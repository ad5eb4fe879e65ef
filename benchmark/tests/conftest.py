"""CPU tests of the benchmark harness: JAX on the CPU with four virtual
devices, Pallas kernels in interpret mode, tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import copy
import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import pytest  # noqa: E402

#: the engine's ring threshold in the tiny worlds, so that a small call
#: takes the ring lane as the cells' large buckets do on the chip
TINY_RING_BYTES = 64 << 10


def tiny_unit(cfg: dict) -> dict:
    """The cell's unit at a size the CPU backend runs in seconds."""
    unit = dict(cfg["unit"])
    if unit["dtype"] == "float32":   # a DDP step: ring, HLO and ragged
        unit["counts"] = [2048, 24576, 30001]
    else:                            # a decode token: 6 chained calls
        unit["counts"] = [4 * 512] * 6
    return unit


@pytest.fixture
def tiny_bench(tmp_path):
    """A copy of the benchmark whose cells run at tiny sizes; returns
    (root, spec)."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        path = tmp_path / c["file"]
        cfg = json.loads(path.read_text())
        cfg["unit"] = tiny_unit(cfg)
        path.write_text(json.dumps(cfg))
    for cell in spec["workloads"]:
        path = tmp_path / "benchmark" / "traffic" / (cell["traffic"] + ".json")
        tr = json.loads(path.read_text())
        tr["warmup_units"] = 2
        if tr["check"]["units"] != "all":
            tr["check"].update(units=1, units_below=1)
        path.write_text(json.dumps(tr))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path, copy.deepcopy(spec)


@pytest.fixture
def run_tiny(tiny_bench):
    """run_tiny(workload, seed=1, trace=0, control=False) -> result."""
    import time

    import harness
    from accl_tpu.utils.bringup import Design, initialize_world

    root, spec = tiny_bench

    def run(workload, seed=1, trace=0, control=False, seconds=0.5):
        cell = harness.find(spec["workloads"], workload, "workload")
        args = harness.parse_args(["--workload", workload, "--seed",
                                   str(seed), "--seconds", str(seconds),
                                   "--trace", str(trace)])
        world = initialize_world(Design.TPU, nranks=cell["chips"])
        world.engine.ring_threshold_bytes = TINY_RING_BYTES
        try:
            return harness.run_cell(args, time.perf_counter(),
                                    require_tpu=False, root=str(root),
                                    here=str(root / "benchmark"),
                                    control=control, world=world)
        finally:
            world.close()

    return run
