"""The two cells this benchmark added, run whole on the CPU at a tiny
size, and their readers on synthetic runs.

``ep_alltoall.4chip`` runs the Moonlight layer cut to hidden 64, 16
experts (top-6), expert width 32, two layers and 128 tokens a chip, with
the traffic file's bias cut to 16 experts, and one kept unit.  A sound
run is correct; the fp8 control and each fault fail a check:

- dropped: every rank's first received row of each alltoallv zeroed;
- wrong_rank: each rank's alltoallv receive is its left neighbour's;
- bf16_router: the program's router computed in bf16;
- short window: a planned (unit, layer) pair the window never reached.

``tp_decode_plan.1chip`` replays captured plans of the tp_decode token:
sound is exact, the control and a stale result are not.
"""
import json
import math
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from accl_tpu.backends import tpu as tpu_backend

EP = "ep_alltoall.4chip"
#: at this width the program reads 7.9e-3 to 9.1e-3 and the fp8 control
#: 8.0e-2 to 8.6e-2 (8 seeds each, CPU): the tiny cell's own limit
TINY_MOE_REL_ERR = 2.5e-2


@pytest.fixture
def run_ep(tiny_bench):
    """run_ep(seed=1, control=False, trace=0, check=None) -> result of
    the tiny ep_alltoall.4chip, `check` updating the traffic's check."""
    from accl_tpu.utils.bringup import Design, initialize_world

    root, spec = tiny_bench
    path = root / "benchmark" / "configs" / \
        "moonlight-16b-a3b-ep-prefill.json"
    cfg = json.loads(path.read_text())
    cfg.update(hidden_size=64, n_routed_experts=16, moe_intermediate_size=32,
               num_hidden_layers=2)
    cfg["assumed"]["prompt_tokens_per_chip"] = 128
    path.write_text(json.dumps(cfg))
    tpath = root / "benchmark" / "traffic" / "ep_moe_prefill.json"
    tr = json.loads(tpath.read_text())
    tr["routing"]["selection_bias"] = tr["routing"]["selection_bias"][:16]
    tr["check"].update(units=1, units_below=1, layers_per_unit=2,
                       token_block=64, moe_rel_err=TINY_MOE_REL_ERR)

    def run(seed=1, control=False, trace=0, check=None):
        tpath.write_text(json.dumps(
            dict(tr, check=dict(tr["check"], **(check or {})))))
        args = harness.parse_args(["--workload", EP, "--seed", str(seed),
                                   "--seconds", "0.5", "--trace",
                                   str(trace)])
        world = initialize_world(Design.TPU, nranks=4)
        try:
            return harness.run_cell(args, time.perf_counter(),
                                    require_tpu=False, root=str(root),
                                    here=str(root / "benchmark"),
                                    control=control, world=world)
        finally:
            world.close()

    return run


def test_ep_sound_run_is_correct(run_ep):
    out = run_ep(seed=2**33 + 9)
    assert out["correct"], out
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert c["a2av_max_abs_err"] == 0 and c["routing_mismatch"] == 0
    assert c["moe_rel_err"] < TINY_MOE_REL_ERR
    assert c["lane_misses"] == 0 and c["window_compiles"] == 0
    # three exchanges of the last unit's last layer on 4 ranks, and the
    # kept layers' tokens
    assert c["outputs_compared"] > 12
    assert {"token_ms", "token_p95_ms", "setup_s"} <= set(out["metrics"])
    assert out["failed"] == 0 and out["attempted"] > 0


def test_ep_control_is_not_correct(run_ep):
    out = run_ep(seed=5, control=True)
    assert not out["correct"]
    err = out["checks"]["moe_rel_err"]
    assert err["limit"] < err["value"] < math.inf
    assert out["checks"]["a2av_max_abs_err"]["value"] == 0


def test_ep_unit_the_window_missed_is_not_correct(run_ep):
    # two units drawn below a million: the half-second window reaches
    # neither, and the check must say so rather than compare fewer pairs
    out = run_ep(seed=17, check={"units": 2, "units_below": 10**6})
    assert not out["correct"]
    assert out["checks"]["moe_rel_err"]["value"] == math.inf


def _dropped(y, c, nranks, recv_rows, row):
    return y.reshape(nranks, recv_rows, row).at[:, 0].set(0).reshape(-1), c


def _wrong_rank(y, c, nranks, recv_rows, row):
    return (jnp.roll(y.reshape(nranks, -1), 1, axis=0).reshape(-1),
            jnp.roll(c.reshape(nranks, -1), 1, axis=0).reshape(-1))


EXCHANGE_FAULTS = {"dropped": _dropped, "wrong_rank": _wrong_rank}


@pytest.mark.parametrize("fault", sorted(EXCHANGE_FAULTS))
def test_ep_exchange_fault_is_not_correct(run_ep, monkeypatch, fault):
    orig = tpu_backend._alltoallv_fn

    def faulty(mesh, nranks, send_rows, recv_rows, row, dtype):
        prog = orig(mesh, nranks, send_rows, recv_rows, row, dtype)

        def run(xc):
            y, c = prog(xc)
            return EXCHANGE_FAULTS[fault](y, c, nranks, recv_rows, row)
        return run

    monkeypatch.setattr(tpu_backend, "_alltoallv_fn", faulty)
    out = run_ep(seed=11)
    assert not out["correct"], out
    assert out["checks"]["a2av_max_abs_err"]["value"] > 0


@pytest.fixture
def bf16_router(monkeypatch):
    from accl_tpu.models import moe_ep

    orig = moe_ep._router

    def router(cfg, x, p):
        p = dict(p, router=p["router"].astype(jnp.bfloat16).astype(
            jnp.float32))
        h, _sel, _g = orig(cfg, x, p)
        hb = h.astype(jnp.bfloat16).astype(jnp.float32)
        s = jax.nn.sigmoid(jnp.dot(hb, p["router"].T).astype(jnp.bfloat16)
                           .astype(jnp.float32))
        _, sel = jax.lax.top_k(s + p["bias"], cfg.top_k)
        w = jnp.take_along_axis(s, sel, axis=1)
        return h, sel, cfg.scaling * w / jnp.sum(w, -1, keepdims=True)

    jax.clear_caches()
    monkeypatch.setattr(moe_ep, "_router", router)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def test_ep_bf16_router_is_not_correct(run_ep, bf16_router):
    out = run_ep(seed=13)
    assert not out["correct"], out
    assert out["checks"]["routing_mismatch"]["value"] > 0


def test_round_e4m3_is_float8_e4m3fn():
    import ml_dtypes

    ep_moe = harness.load_module(harness.HERE, "traffic", "ep_moe")
    rng = np.random.default_rng(0)
    v = (rng.standard_normal(50_000)
         * np.exp(rng.uniform(-12, 6, 50_000))).astype(np.float32)
    v = np.r_[np.clip(v, -448, 448), 0.0, 448.0, -448.0, 2.0 ** -9,
              2.0 ** -10, 3 * 2.0 ** -10, 1.0625, 1.1875].astype(np.float32)
    got = np.asarray(ep_moe.round_e4m3(jnp.asarray(v)))
    want = v.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    assert np.array_equal(got, want)


def test_carry_fp8_scales_each_128_block():
    import ml_dtypes

    ep_moe = harness.load_module(harness.HERE, "traffic", "ep_moe")
    rng = np.random.default_rng(1)
    w = rng.standard_normal((2, 200, 130)).astype(np.float32)
    w[0, :128, :128] *= 1e-3   # a block of its own scale
    w[1, 128:, 128:] = 0.0     # an all-zero edge block
    got = np.asarray(ep_moe.carry_fp8(jnp.asarray(w)))
    want = np.zeros_like(w)
    for i in range(2):
        for r in range(0, 200, 128):
            for c in range(0, 130, 128):
                b = w[i, r:r + 128, c:c + 128]
                amax = np.abs(b).max()
                scale = np.float32(amax / 448 if amax else 1.0)
                want[i, r:r + 128, c:c + 128] = (b / scale).astype(
                    ml_dtypes.float8_e4m3fn).astype(np.float32) * scale
    assert np.allclose(got, want, rtol=1e-6, atol=0)
    assert 0.01 < np.linalg.norm(got - w) / np.linalg.norm(w) < 0.04


def test_alltoallv_reference_packs_in_source_order():
    op = harness.load_module(harness.HERE, "ops", "alltoallv_counts")
    sends = [np.arange(8, dtype=np.float32) + 100 * i for i in range(2)]
    counts = [np.array([1, 3]), np.array([2, 2])]
    (r0, c0), (r1, c1) = op.reference(sends, counts, 2, 16)
    assert c0.tolist() == [1, 2] and c1.tolist() == [3, 2]
    assert r0.tolist() == [0, 1, 100, 101, 102, 103]
    assert r1.tolist() == [2, 3, 4, 5, 6, 7, 104, 105, 106, 107]
    assert op.max_abs_err(np.r_[r1, 9.0, 9.0], r1) == 0.0
    with pytest.raises(ValueError, match="more than"):
        op.reference(sends, counts, 2, 4)


# -- readers on synthetic runs ------------------------------------------
RAGGED = "%ragged_all_to_all.7 = bf16[196608,2,1024] ragged-all-to-all"
RESHAPE = "%ragged_all_to_all.6 = bf16[49152,2,1024] reshape"


def _ep_run(ops, busy_s=10.0, window_s=20.0, units=20, spans=None):
    spec = harness.load_spec()
    cfg = harness.load_config(spec, "moonlight-16b-a3b-ep-prefill")
    calls = [c for _ in range(26) for c in (
        ("alltoall", 256), ("alltoallv", 150e6), ("alltoallv", 150e6))]
    trace = SimpleNamespace(ops=ops, busy_s=busy_s, window_s=window_s)
    return harness.Run(cell={}, config=cfg, traffic={}, nranks=4,
                       device_kind="TPU v5 lite", window_s=window_s,
                       unit_s=[window_s / units] * units, calls=calls,
                       spans=spans, trace=trace)


def test_ep_readers_on_a_synthetic_trace():
    run = _ep_run([(RAGGED, 2.08), (RESHAPE, 0.5), ("%fusion.1 = f32[8] "
                                                    "fusion", 6.0)])
    calls = 20 * 52
    read = {n: harness.load_reader(n)(run) for n in (
        "a2av_device_us.ep", "a2av_ici_share.ep", "moe_mxu_share.ep",
        "device_idle.ep")}
    assert read["a2av_device_us.ep"] == pytest.approx(2.08 / calls * 1e6)
    # 52 calls x 150 MB x 20 units over 2.08 s, of 200 GB/s
    assert read["a2av_ici_share.ep"] == pytest.approx(
        100 * 52 * 150e6 * 20 / 2.08 / 200e9)
    # 26 layers x 20 units of (router + 49152 rows x 3 projections +
    # shared) over 10 - 2.08 s of 197 TFLOP/s
    T, rows = 8192, 8192 * 6
    layer = 2 * T * 2048 * 64 + 6 * rows * 2048 * 1408 + 6 * T * 2048 * 2816
    assert read["moe_mxu_share.ep"] == pytest.approx(
        100 * layer * 26 * 20 / (10 - 2.08) / 197e12)
    assert read["device_idle.ep"] == pytest.approx(50.0)


def test_ep_readers_find_nothing_without_the_exchange():
    run = _ep_run([("%fusion.1 = f32[8] fusion", 6.0)])
    assert harness.load_reader("a2av_device_us.ep")(run) is None
    assert harness.load_reader("a2av_ici_share.ep")(run) is None
    none = _ep_run([])
    none.trace = None
    for name in ("a2av_device_us.ep", "a2av_ici_share.ep",
                 "moe_mxu_share.ep", "device_idle.ep", "host_us.ep"):
        assert harness.load_reader(name)(none) is None


def test_host_us_ep_is_the_median_submit_to_dispatch():
    spans = [SimpleNamespace(t_submit=0, t_dispatch=d * 1000)
             for d in (100, 300, 200)]
    spans.append(SimpleNamespace(t_submit=0, t_dispatch=None))
    run = _ep_run([], spans=spans)
    assert harness.load_reader("host_us.ep")(run) == pytest.approx(200.0)


# -- tp_decode_plan.1chip -------------------------------------------------
def test_tp_plan_sound_run_replays_and_is_exact(run_tiny):
    out = run_tiny("tp_decode_plan.1chip", seed=2**31 + 3)
    assert out["correct"], out
    assert out["checks"]["max_rel_err"]["value"] == 0
    assert out["checks"]["lane_misses"]["value"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0


def test_tp_plan_control_and_stale_are_not_correct(run_tiny, monkeypatch):
    out = run_tiny("tp_decode_plan.1chip", seed=5, control=True)
    assert not out["correct"]
    monkeypatch.setattr(tpu_backend.TpuEngine, "_scatter_back",
                        lambda self, plan, y: None)
    assert not run_tiny("tp_decode_plan.1chip", seed=7)["correct"]
