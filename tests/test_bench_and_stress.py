"""Benchmark sweep harness + stress tests.

- sweep: reference ACCLSweepBenchmark (bench.cpp:25-61) — here a short
  range in CI; the full 2^4..2^19 sweep runs via scripts/run_sweep.py
- stress: the reference 2000-iteration ring send/recv
  (test/host/xrt/src/stress.cpp:24-34)
"""
import io

import numpy as np
import pytest

from accl_tpu.backends.emu import EmuWorld
from accl_tpu.bench import SweepConfig, run_sweep
from accl_tpu.utils.bringup import Design, generate_ranks, initialize_world


def test_sweep_emulator():
    cfg = SweepConfig(count_pows=(4, 8), repetitions=1)
    out = io.StringIO()
    with EmuWorld(2) as world:
        rows = run_sweep(world, cfg, writer=out)
    assert len(rows) == len(cfg.collectives) * 2
    csv_text = out.getvalue()
    assert "allreduce" in csv_text and "busbw_GBps" in csv_text
    for r in rows:
        assert r["duration_us"] > 0


def test_sweep_tpu_backend():
    from accl_tpu.backends.tpu import TpuWorld

    cfg = SweepConfig(collectives=("allreduce", "allgather"),
                      count_pows=(6,), repetitions=1)
    with TpuWorld(4) as world:
        rows = run_sweep(world, cfg)
    assert len(rows) == 2


def test_stress_ring_sendrecv():
    # reference stress.cpp: 2000 iterations; trimmed for CI wall clock
    iters, count = 500, 32
    with EmuWorld(2) as world:
        def fn(accl, rank):
            nxt, prv = (rank + 1) % 2, (rank - 1) % 2
            src = accl.create_buffer_like(
                np.full(count, float(rank), np.float32))
            dst = accl.create_buffer(count, np.float32)
            for i in range(iters):
                sreq = accl.send(src, count, nxt, tag=i % 7, run_async=True)
                accl.recv(dst, count, prv, tag=i % 7)
                assert sreq.wait(30)
                sreq.check()
            np.testing.assert_array_equal(
                dst.host, np.full(count, float(prv), np.float32))

        world.run(fn)


def test_generate_ranks_and_bringup():
    ranks = generate_ranks(4, base_port=6000)
    assert len(ranks) == 4 and ranks[2].port == 6002
    with initialize_world(Design.EMU_INPROC, 2) as world:
        from accl_tpu import ReduceFunction

        def fn(accl, rank):
            a = accl.create_buffer_like(np.ones(8, np.float32))
            b = accl.create_buffer(8, np.float32)
            accl.allreduce(a, b, 8, ReduceFunction.SUM)
            return float(b.host[0])

        assert world.run(fn) == [2.0, 2.0]


def _import_baseline_bench():
    import importlib
    import os
    import sys

    scripts = os.path.join(os.path.dirname(__file__), os.pardir, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    return importlib.import_module("baseline_bench")


# ---------------------------------------------------------------------------
# the five benchmark configs of record (BASELINE.json / BASELINE.md) run
# end-to-end in miniature
# ---------------------------------------------------------------------------
def test_baseline_config1_cpu_baseline():
    import io
    baseline_bench = _import_baseline_bench()

    rows = baseline_bench.config1(io.StringIO(), reps=1)
    assert {r["collective"] for r in rows} == {"allreduce"}
    assert all(r["duration_us"] > 0 for r in rows)


def test_baseline_config3_bf16_fp16():
    import io
    baseline_bench = _import_baseline_bench()

    rows = baseline_bench.config3(io.StringIO(), reps=1)
    colls = {r["collective"] for r in rows}
    assert colls == {"allgather", "reduce_scatter"}


def test_baseline_config5_fusion():
    import io
    baseline_bench = _import_baseline_bench()

    rows = baseline_bench.config5(io.StringIO(), reps=1)
    by = {r["variant"]: r for r in rows}
    assert by["fused"]["seconds"] > 0 and by["unfused"]["seconds"] > 0


def test_parse_bench_results_roundtrip(tmp_path):
    # the postprocessing pair of the reference (parse_bench_results.py /
    # Coyote plot.py): sweep CSV -> median table + ratio vs a baseline
    import importlib.util
    import io as _io
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                        "parse_bench_results.py")
    spec = importlib.util.spec_from_file_location("parse_bench_results", path)
    parse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(parse)

    csv_text = (
        "collective,count,bytes,duration_us,algbw_GBps,busbw_GBps,repetition\n"
        "allreduce,16,64,10.0,0.006,0.009,0\n"
        "allreduce,16,64,20.0,0.004,0.006,1\n"
        "allreduce,32,128,10.0,0.012,0.018,0\n")
    p = tmp_path / "sweep.csv"
    p.write_text(csv_text)
    data = parse.load(str(p))
    assert data[("allreduce", 16)]["dur_us"] == 15.0  # median of reps
    out = _io.StringIO()
    parse.report(data, baseline=data, out=out)
    text = out.getvalue()
    assert "allreduce" in text and "1.00x" in text and "peak busbw" in text


def _load_bench(name="bench_mod"):
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        name, _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_stage_functions_smoke(monkeypatch):
    """Structurally execute every TPU bench stage's operand
    construction + reporting logic with a FAKE timing harness, so a
    NameError/typo in chip-only code fails in CI instead of on the
    chip.  Stages raise: the compiled self-ring cannot run on the CPU,
    and its stage must fail rather than record an error."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    bench = _load_bench("bench_mod2")

    def fake_chain(fn, x0, iters, trials=1, consts=()):
        return 1e-3  # plausible per-iteration seconds; never executes

    detail = bench._flash_stage(jax, jnp, fake_chain)
    # the reporting paths must have produced the headline flash keys
    assert "flash_d128_tflops" in detail, detail
    assert "flash_attention_tflops" in detail, detail
    # the TPU lowering of fwd+bwd holds all three pallas calls, so the
    # composite is reported at plausible times ...
    assert detail["flash_fwdbwd_pallas_calls"] >= 3, detail
    assert "flash_d128_fwdbwd_tflops" in detail, detail

    def fast_bwd_chain(fn, x0, iters, trials=1, consts=()):
        return 1e-6 if iters == 24 else 1e-3  # fwd+bwd chains: 24 iters

    # ... and a composite faster than the matmul peak fails CLOSED (no
    # DCE-style inflated number can slip out)
    detail = bench._flash_stage(jax, jnp, fast_bwd_chain)
    assert "flash_d128_fwdbwd_tflops" not in detail, detail
    assert "flash_d128_fwdbwd_inconsistent" in detail, detail

    detail = bench._flash_variants_stage(jax, jnp, fake_chain)
    assert "flash_d128_packed_all" in detail, detail
    assert "flash_d64_packed_all" in detail, detail

    def fake_ab(fns, x0, iters, trials=1, consts=()):
        return {k: 1e-3 for k in fns}

    detail = bench._compression_stage(jax, jnp, fake_ab)
    assert detail["compression_gbps"] == detail["compression_xla_gbps"]

    with pytest.raises(Exception, match="interpret"):
        bench._selfring_stage(jax, jnp, fake_chain)


