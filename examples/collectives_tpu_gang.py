"""The same driver API on the TPU backend — switching rungs, not code.

The point of the rung ladder: the imperative per-rank driver program
from examples/collectives_emu.py runs unchanged against the TPU
backend, where each rank's buffers live on a device of the mesh and
every matched gang of calls executes as ONE AOT-compiled XLA SPMD
collective over ICI (backends/tpu.py).  Here: 4 virtual CPU devices
standing in for 4 TPU chips — on real hardware only the platform pin
changes.

    python examples/collectives_tpu_gang.py
"""
import os
import sys
import threading

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from accl_tpu.utils.platform import ensure_host_device_count

ensure_host_device_count(4)

import jax

# virtual CPU devices stand in for chips; ACCL_EXAMPLE_ON_TPU=1 runs on
# the TPU instead
if not os.environ.get("ACCL_EXAMPLE_ON_TPU"):
    jax.config.update("jax_platforms", "cpu")

from accl_tpu.constants import DataType, ReduceFunction
from accl_tpu.utils.bringup import Design, initialize_world

NRANKS = 4
COUNT = 1024


def rank_main(world, r, results):
    a = world.accls[r]
    src = a.create_buffer(COUNT, np.float32)
    out = a.create_buffer(COUNT, np.float32)
    src.host[:] = np.arange(COUNT, dtype=np.float32) + 1000 * r

    # the gang scheduler pairs the four ranks' descriptors and runs one
    # compiled psum over the mesh (repeat calls hit the plan cache)
    a.allreduce(src, out, COUNT, ReduceFunction.SUM)
    expect = (np.arange(COUNT, dtype=np.float32) * NRANKS
              + 1000 * sum(range(NRANKS)))
    np.testing.assert_allclose(out.host, expect, rtol=1e-5)

    # compressed wire representation on the same backend
    outc = a.create_buffer(COUNT, np.float32)
    a.allreduce(src, outc, COUNT, ReduceFunction.SUM,
                compress_dtype=DataType.float16)
    np.testing.assert_allclose(outc.host, expect, rtol=2e-3, atol=4.0)

    results[r] = "ok"


def main():
    world = initialize_world(Design.TPU, nranks=NRANKS)
    try:
        results = {}
        threads = [threading.Thread(target=rank_main,
                                    args=(world, r, results))
                   for r in range(NRANKS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results.get(r) == "ok" for r in range(NRANKS)), results
        print(f"collectives_tpu_gang: {NRANKS} ranks x gang allreduce "
              "(plain + fp16 wire) as compiled SPMD collectives: OK")
    finally:
        world.close()


if __name__ == "__main__":
    main()
