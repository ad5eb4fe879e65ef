"""Capture the busbw sweep artifacts of record into bench/results/.

Produces the CSV shapes BASELINE.md names as the metric of record
(busbw-vs-size tables, nccl conventions — reference bench harness
test/host/xrt/src/bench.cpp:25-61 + parse_bench_results.py):

  sweep_emu_r{N}.csv       driver busbw over the native engine (4 ranks,
                           inproc transport)
  sweep_dgram_r{N}.csv     same matrix over the adversarial datagram rung
  sweep_rdma_r{N}.csv      same matrix over the queue-pair RDMA rung
  sweep_tpu8_r{N}.csv      driver busbw over the TPU backend gang
                           scheduler on the 8-virtual-device CPU mesh
  driver_vs_raw_r{N}.csv   allreduce latency through the FULL driver
                           stack vs a bare jitted shard_map psum on the
                           same mesh (the Coyote harness's ACCL-vs-MPI
                           comparison role, plot.py:10-44)
  sweep_{emu,dgram,rdma,tpu8}_f16_r{N}.csv  fp16 allreduce sweep on
                           every rung (the metric of record names
                           fp32/fp16) through the f16 arithmetic lanes
  pipeline_ab_r{N}.csv     eager egress pipelining A/B (depth 1 vs 3)
                           across message sizes on the emulator

CPU-rung absolute numbers are NOT hardware numbers — they are recorded
so the busbw-vs-size SHAPE and the pipelining delta are inspectable and
regressions show in review diffs.

Usage: python scripts/capture_sweeps.py [--round 3]
"""
from __future__ import annotations

import argparse
import csv
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--round", type=int, default=4)
    ap.add_argument("--stages",
                    default="emu,dgram,rdma,tpu8,f16,f16all,vsraw,pipeline",
                    help="comma list of stages to run")
    ap.add_argument("--maxpow", type=int, default=19,
                    help="largest 2^k element count (BASELINE metric of "
                         "record: 2^4..2^19, reference bench.cpp:25-61)")
    ap.add_argument("--outdir", default=os.path.join("bench", "results"))
    args = ap.parse_args()

    from accl_tpu.utils.platform import ensure_host_device_count

    ensure_host_device_count(8)

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np  # noqa: F401

    from accl_tpu.backends.emu import EmuWorld
    from accl_tpu.bench.sweep import SweepConfig, run_sweep

    os.makedirs(args.outdir, exist_ok=True)
    tag = f"r{args.round:02d}"
    stages = set(args.stages.split(","))

    # 1. emulator rung (counts kept moderate: 1 core drives 4 engines)
    def raise_timeouts(w):
        # 1 core drives every engine; rendezvous retries under load need
        # far more than the 1s default receive budget
        for a in w.accls:
            a.set_timeout(60_000_000)
            a.call_timeout_s = 180.0
        return w

    def prep_tpu_world(w):
        # the full-range virtual sweeps ride the XLA collective path:
        # the interpreted Pallas ring at multi-MB payloads measures the
        # interpreter, not the driver (ring correctness at 8 ranks is
        # certified by dryrun_multichip with the threshold forced to 0)
        w.engine.ring_threshold_bytes = 1 << 60
        for a in w.accls:
            a.call_timeout_s = 180.0  # 1 core drives all 8 gang members
        return w

    def make_emu_world(**extra):
        # ONE provisioning for every emulator-rung sweep: rx pool sized
        # for the worst eager case ((P-1) peers x 16 segments at the
        # 16 KB ceiling, the reference bench's sizing), 256MB devicemem
        # + 64MB rendezvous cap for the 2^19 large-message regime
        return EmuWorld(4, devmem_bytes=256 << 20, n_egr_rx_bufs=64,
                        max_eager_size=16384,
                        max_rendezvous_size=64 << 20, **extra)

    cfg = SweepConfig(count_pows=tuple(range(4, args.maxpow + 1)),
                      repetitions=3)
    if "emu" in stages:
        path = os.path.join(args.outdir, f"sweep_emu_{tag}.csv")
        with make_emu_world() as w, open(path, "w", newline="") as f:
            run_sweep(raise_timeouts(w), cfg, writer=f)
        print(f"wrote {path}")

    # 2. datagram rung (fragmentation + reorder on every transfer)
    if "dgram" in stages:
        path = os.path.join(args.outdir, f"sweep_dgram_{tag}.csv")
        with make_emu_world(transport="dgram", mtu=512,
                            reorder_window=8) as w, \
                open(path, "w", newline="") as f:
            run_sweep(raise_timeouts(w), cfg, writer=f)
        print(f"wrote {path}")

    # 2b. RDMA rung (queue pairs; one-sided memory plane for rendezvous)
    if "rdma" in stages:
        path = os.path.join(args.outdir, f"sweep_rdma_{tag}.csv")
        with make_emu_world(transport="rdma") as w, \
                open(path, "w", newline="") as f:
            run_sweep(raise_timeouts(w), cfg, writer=f)
        print(f"wrote {path}")

    # 3. TPU backend gang scheduler on the virtual 8-device mesh
    from accl_tpu.backends.tpu import TpuWorld

    if "tpu8" in stages:
        path = os.path.join(args.outdir, f"sweep_tpu8_{tag}.csv")
        with TpuWorld(8) as w, open(path, "w", newline="") as f:
            run_sweep(prep_tpu_world(w), SweepConfig(
                count_pows=tuple(range(4, args.maxpow + 1)),
                repetitions=3), writer=f)
        print(f"wrote {path}")

    # 3c. fp16 allreduce sweep (BASELINE metric of record names
    #     "fp32/fp16"): the f16 arithmetic lanes end to end on the
    #     emulator rung + the TPU-backend gang
    if "f16" in stages:
        cfg16 = SweepConfig(collectives=("allreduce",),
                            count_pows=tuple(range(4, args.maxpow + 1)),
                            dtype="float16", repetitions=3)
        path = os.path.join(args.outdir, f"sweep_emu_f16_{tag}.csv")
        with make_emu_world() as w, open(path, "w", newline="") as f:
            run_sweep(raise_timeouts(w), cfg16, writer=f)
        print(f"wrote {path}")
        path = os.path.join(args.outdir, f"sweep_tpu8_f16_{tag}.csv")
        with TpuWorld(8) as w, open(path, "w", newline="") as f:
            run_sweep(prep_tpu_world(w), cfg16, writer=f)
        print(f"wrote {path}")

    # 3d. f16 on the lossy/datagram and RDMA rungs too ("f16all"),
    # completing the fp32+fp16 matrix across every transport rung
    if "f16all" in stages:
        cfg16 = SweepConfig(collectives=("allreduce",),
                            count_pows=tuple(range(4, args.maxpow + 1)),
                            dtype="float16", repetitions=3)
        for rung, kw in (("dgram", dict(transport="dgram", mtu=512,
                                        reorder_window=8)),
                         ("rdma", dict(transport="rdma"))):
            path = os.path.join(args.outdir,
                                f"sweep_{rung}_f16_{tag}.csv")
            with make_emu_world(**kw) as w, \
                    open(path, "w", newline="") as f:
                run_sweep(raise_timeouts(w), cfg16, writer=f)
            print(f"wrote {path}")

    # 3b + 4: the remaining stages self-select below
    if "vsraw" in stages:
        _vsraw_stage(args, tag, TpuWorld)
    _pipeline_stage(args, tag, stages, EmuWorld)


def _vsraw_stage(args, tag, TpuWorld) -> None:
    # driver path vs raw XLA collective across the sweep — the Coyote
    # harness's ACCL-vs-MPI comparison role (reference
    # test/host/Coyote/run_scripts/plot.py:10-44): same mesh, same
    # payload, allreduce through the full driver stack vs a bare jitted
    # shard_map psum.  The ratio column is the driver's end-to-end
    # overhead at each size.
    import jax as _jax
    import jax.numpy as _jnp
    import numpy as _np
    from jax.sharding import Mesh as _Mesh, NamedSharding as _NS, PartitionSpec as _P

    path = os.path.join(args.outdir, f"driver_vs_raw_{tag}.csv")
    with TpuWorld(8) as w, open(path, "w", newline="") as f:
        w.engine.ring_threshold_bytes = 1 << 60
        for a in w.accls:
            a.call_timeout_s = 180.0
        wcsv = csv.DictWriter(f, fieldnames=[
            "count", "bytes", "driver_us", "raw_us", "overhead_x"])
        wcsv.writeheader()

        devs = _jax.devices()[:8]
        mesh = _Mesh(_np.array(devs), ("rank",))

        def driver_best(count, reps=5):
            def body(accl, rank):
                import numpy as np
                s = accl.create_buffer(count, np.float32)
                r = accl.create_buffer(count, np.float32)
                s.host[:] = rank
                from accl_tpu import ReduceFunction
                accl.allreduce(s, r, count, ReduceFunction.SUM)  # warm
                best = 1e30
                for _ in range(reps):
                    t0 = time.perf_counter()
                    accl.allreduce(s, r, count, ReduceFunction.SUM,
                                   from_fpga=True, to_fpga=True)
                    best = min(best, time.perf_counter() - t0)
                for b in (s, r):
                    free = getattr(b, "free", None)
                    if free:
                        free()
                return best
            return max(w.run(body))

        def raw_best(count, reps=5):
            x = _jax.device_put(
                _jnp.zeros((8 * count,), _jnp.float32),
                _NS(mesh, _P("rank")))
            fn = _jax.jit(_jax.shard_map(
                lambda v: _jax.lax.psum(v, "rank"), mesh=mesh,
                in_specs=_P("rank"), out_specs=_P("rank")))
            _jax.block_until_ready(fn(x))
            best = 1e30
            for _ in range(reps):
                t0 = time.perf_counter()
                _jax.block_until_ready(fn(x))
                best = min(best, time.perf_counter() - t0)
            return best

        for pw in range(4, args.maxpow + 1):
            count = 1 << pw
            d_us = driver_best(count) * 1e6
            r_us = raw_best(count) * 1e6
            wcsv.writerow({
                "count": count,
                "bytes": count * 4,
                "driver_us": round(d_us, 1),
                "raw_us": round(r_us, 1),
                "overhead_x": round(d_us / max(r_us, 1e-9), 2),
            })
    print(f"wrote {path}")


def _pipeline_stage(args, tag, stages, EmuWorld) -> None:
    # 4. egress pipelining A/B: depth 1 (strictly serial, the round-2
    #    engine's behavior) vs depth 3 (reference discipline) across
    #    multi-segment message sizes
    if "pipeline" not in stages:
        return
    path = os.path.join(args.outdir, f"pipeline_ab_{tag}.csv")
    with open(path, "w", newline="") as f:
        wcsv = csv.DictWriter(f, fieldnames=[
            "count", "bytes", "depth", "mean_us", "best_us", "reps"])
        wcsv.writeheader()
        for depth in (1, 3):
            with EmuWorld(2, max_eager_size=1 << 20,
                          max_rendezvous_size=64 << 20) as w:
                def fn(accl, rank, count, depth=depth):
                    import numpy as np
                    accl.set_tuning(3, depth)  # EGRESS_PIPELINE_DEPTH
                    nxt, prv = (rank + 1) % 2, (rank - 1) % 2
                    src = accl.create_buffer(count, np.float32)
                    dst = accl.create_buffer(count, np.float32)
                    src.host[:] = rank
                    durs = []
                    for rep in range(7):
                        t0 = time.perf_counter()
                        req = accl.send(src, count, nxt, tag=rep,
                                        run_async=True)
                        accl.recv(dst, count, prv, tag=rep)
                        req.wait(60)
                        durs.append(time.perf_counter() - t0)
                    return durs[2:]  # drop warmup reps

                for pw in range(8, 17):
                    count = 1 << pw
                    per_rank = w.run(fn, count)
                    durs = [d for ds in per_rank for d in ds]
                    wcsv.writerow({
                        "count": count,
                        "bytes": count * 4,
                        "depth": depth,
                        "mean_us": round(statistics.mean(durs) * 1e6, 1),
                        "best_us": round(min(durs) * 1e6, 1),
                        "reps": len(durs),
                    })
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
