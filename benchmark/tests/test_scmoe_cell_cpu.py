"""The two cells this configuration brought, run whole on the CPU at a
tiny size, and their readers on synthetic runs.

``scmoe_decode.1chip`` runs the LongCat-Flash layer cut to hidden 64,
dense FFNs of width 96, 32 routed experts of width 32 (8 held, the
traffic's hottest) and 16 zero-compute ones, top-12 softmax routing,
two layers and 32 tokens a step.  A sound run is correct; the fp8
control and each fault fail a check:

- zero_experts: the expert program's output zeroed;
- unbiased: the program's router selects without the selection bias;
- renormalised: the program's gates renormalised over the picks;
- short window: a planned (unit, layer) pair the window never reached.

``tp_decode.4chip``, deferred (its traffic is here, the cell not yet in
BENCHMARK.json), chains the tp_decode token's allreduces over four ranks
with a x0.25 scale between calls: sound is correct, the control and a
stale result are not.
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

SC = "scmoe_decode.1chip"
CONFIG = "longcat-flash-chat-scmoe-decode"
#: at this width the program reads 7.1e-3 to 8.9e-3 (layer) and 5.9e-3
#: to 8.2e-3 (expert rows), the fp8 control 5.4e-2 to 6.9e-2 and 8.7e-2
#: to 9.7e-2 (4 seeds each, CPU): the tiny cell's own limits
TINY_SCMOE_REL_ERR = 2e-2
TINY_EXPERTS_REL_ERR = 2e-2
#: the tiny cell compares about 96 tokens, so one near-tie (gap under
#: 1e-7, seen on some windows' last units) reads 1.04%: its own limit
TINY_ROUTING_TIES = 5e-2


def _tiny_scmoe(root):
    path = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    order = np.random.default_rng(0).permutation(32)
    cfg.update(hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32,
               num_layers=2, zero_expert_num=16,
               published={"num_layers": 2, "n_routed_experts": 32})
    cfg["deployment"]["held_experts"] = sorted(order[:8].tolist())
    cfg["assumed"]["decode_tokens_per_chip"] = 32
    path.write_text(json.dumps(cfg))
    tpath = root / "benchmark" / "traffic" / "scmoe_decode.json"
    tr = json.loads(tpath.read_text())
    tr["routing"].update(bias_scale=0.01, zero_bias=0.002)
    tr["check"].update(units=1, units_below=1, layers_per_unit=2,
                       scmoe_rel_err=TINY_SCMOE_REL_ERR,
                       experts_rel_err=TINY_EXPERTS_REL_ERR,
                       routing_ties=TINY_ROUTING_TIES)
    return tpath, tr


@pytest.fixture
def run_sc(tiny_bench):
    """run_sc(seed=1, control=False, trace=0, check=None) -> result of
    the tiny scmoe_decode.1chip, `check` updating the traffic's check."""
    from accl_tpu.utils.bringup import Design, initialize_world

    root, _spec = tiny_bench
    tpath, tr = _tiny_scmoe(root)

    def run(seed=1, control=False, trace=0, check=None):
        tpath.write_text(json.dumps(
            dict(tr, check=dict(tr["check"], **(check or {})))))
        args = harness.parse_args(["--workload", SC, "--seed", str(seed),
                                   "--seconds", "0.5", "--trace",
                                   str(trace)])
        world = initialize_world(Design.TPU, nranks=1)
        try:
            return harness.run_cell(args, time.perf_counter(),
                                    require_tpu=False, root=str(root),
                                    here=str(root / "benchmark"),
                                    control=control, world=world)
        finally:
            world.close()

    return run


def _values(out):
    return {k: v["value"] for k, v in out["checks"].items()}


def test_scmoe_sound_run_is_correct(run_sc):
    out = run_sc(seed=2**33 + 9)
    assert out["correct"], out
    c = _values(out)
    assert c["routing_mismatch"] == 0
    assert 0 < c["scmoe_rel_err"] < TINY_SCMOE_REL_ERR
    assert 0 < c["experts_rel_err"] < TINY_EXPERTS_REL_ERR
    assert c["lane_misses"] == 0 and c["window_compiles"] == 0
    assert c["outputs_compared"] > 2 * 32
    assert {"token_ms", "token_p95_ms", "setup_s"} == set(out["metrics"])
    assert out["failed"] == 0 and out["attempted"] > 0


def test_scmoe_traced_run_reads_the_host_path(run_sc):
    # the CPU's trace has no TPU plane: the device metrics find nothing
    # and are left out, the span metric reads
    out = run_sc(seed=3, trace=1)
    assert out["correct"], out
    assert set(out["metrics"]) == {"host_us.scmoe"}
    assert out["metrics"]["host_us.scmoe"]["value"] > 0


def test_scmoe_control_is_not_correct(run_sc):
    out = run_sc(seed=5, control=True)
    assert not out["correct"]
    for name in ("scmoe_rel_err", "experts_rel_err"):
        c = out["checks"][name]
        assert c["limit"] < c["value"] < math.inf


def test_scmoe_unit_the_window_missed_is_not_correct(run_sc):
    out = run_sc(seed=17, check={"units": 1, "units_below": 10**6})
    assert not out["correct"]
    assert out["checks"]["scmoe_rel_err"]["value"] == math.inf


def test_scmoe_zeroed_experts_are_not_correct(run_sc, monkeypatch):
    from accl_tpu.models import moe_ep

    orig = moe_ep._experts

    def zeroed(cfg, interpret, rows, counts, p):
        return jnp.zeros_like(orig(cfg, interpret, rows, counts, p))

    monkeypatch.setattr(moe_ep, "_experts", zeroed)
    out = run_sc(seed=11)
    assert not out["correct"], out
    assert out["checks"]["experts_rel_err"]["value"] == pytest.approx(1.0)


def _unbiased(cfg, h, s, p):
    """Selected by the scores alone, the selection bias dropped."""
    _, sel = jax.lax.top_k(s, cfg.top_k)
    return sel, cfg.scaling * jnp.take_along_axis(s, sel, axis=1)


def _renormalised(cfg, h, s, p):
    """The gates renormalised over the picks, as the sigmoid router's."""
    _, sel = jax.lax.top_k(s + p["bias"], cfg.top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    return sel, cfg.scaling * w / jnp.sum(w, -1, keepdims=True)


#: router fault -> the check that must see it
ROUTER_FAULTS = {"unbiased": (_unbiased, "routing_mismatch"),
                 "renormalised": (_renormalised, "scmoe_rel_err")}


@pytest.mark.parametrize("fault", sorted(ROUTER_FAULTS))
def test_scmoe_router_fault_is_not_correct(run_sc, monkeypatch, fault):
    from accl_tpu.models import moe_ep

    orig = moe_ep._router
    wrong, check = ROUTER_FAULTS[fault]

    def router(cfg, x, p):
        h, _sel, _g = orig(cfg, x, p)
        s = jax.nn.softmax(jnp.dot(h, p["router"].T,
                                   precision=jax.lax.Precision.HIGHEST),
                           axis=-1)
        return (h, *wrong(cfg, h, s, p))

    jax.clear_caches()
    monkeypatch.setattr(moe_ep, "_router", router)
    try:
        out = run_sc(seed=13)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not out["correct"], out
    c = out["checks"][check]
    assert c["value"] > c["limit"]


def test_selection_bias_follows_the_traffic():
    gen = harness.load_module(harness.HERE, "traffic", "scmoe_decode")
    tr = harness.load_traffic("scmoe_decode")
    cfg = harness.load_config(harness.load_spec(), CONFIG)
    bias = gen.selection_bias(tr["routing"], 512, 256)
    order = gen.expert_order(tr["routing"], 512)
    assert bias.shape == (768,) and bias.dtype == np.float32
    assert np.all(np.diff(bias[order]) < 0)   # the order is the skew's
    assert np.all(bias[512:] == np.float32(tr["routing"]["zero_bias"]))
    # the chip holds the 8 experts the skew loads most
    assert sorted(order[:8].tolist()) == cfg["deployment"]["held_experts"]
    assert cfg["n_routed_experts"] == len(cfg["deployment"]["held_experts"])


# -- readers on synthetic runs ------------------------------------------
EXPERTS_OP = "%_experts.4 = bf16[2048,2048] custom-call"


def _sc_run(ops, busy_s=10.0, window_s=20.0, units=100, spans=None):
    spec = harness.load_spec()
    cfg = harness.load_config(spec, CONFIG)
    rows = 200
    calls = [c for _ in range(7) for c in (
        ("alltoall", 32), ("alltoallv", rows * 6144 * 2),
        ("alltoallv", rows * 6144 * 2))]
    trace = SimpleNamespace(ops=ops, busy_s=busy_s, window_s=window_s)
    return harness.Run(cell={}, config=cfg, traffic={}, nranks=1,
                       device_kind="TPU v5 lite", window_s=window_s,
                       unit_s=[window_s / units] * units, calls=calls,
                       spans=spans, trace=trace)


def test_scmoe_readers_on_a_synthetic_trace():
    run = _sc_run([(EXPERTS_OP, 1.2), ("%fusion.3 = f32[8] fusion", 6.0),
                   ("%_experts.2 = s32[9] fusion", 0.5)])
    read = {n: harness.load_reader(n)(run) for n in (
        "experts_hbm_share.scmoe", "device_idle.scmoe")}
    # 7 layers x 100 units of 8 experts' weights and 200 rows in and out
    # over 1.2 s of 819 GB/s
    H, F = 6144, 2048
    layer = 3 * 8 * H * F * 2 + 200 * (3 * H + 3 * F) * 2
    assert read["experts_hbm_share.scmoe"] == pytest.approx(
        100 * layer * 7 * 100 / 1.2 / 819e9)
    assert read["device_idle.scmoe"] == pytest.approx(50.0)


def test_scmoe_readers_find_nothing_without_the_kernels():
    run = _sc_run([("%fusion.3 = f32[8] fusion", 6.0)])
    assert harness.load_reader("experts_hbm_share.scmoe")(run) is None
    none = _sc_run([])
    none.trace = None
    for name in ("experts_hbm_share.scmoe", "device_idle.scmoe",
                 "host_us.scmoe"):
        assert harness.load_reader(name)(none) is None


def test_moe_bytes_counts_weights_once_and_rows_in_and_out():
    import moe_bytes

    assert moe_bytes.weight_bytes(6144, 2048, 8) == 603_979_776
    assert moe_bytes.row_bytes(6144, 2048, 1) == 2 * (3 * 6144 + 3 * 2048)
    assert moe_bytes.layer_bytes(6144, 2048, 8, 0) == 603_979_776


# -- tp_decode.4chip ------------------------------------------------------
TP4 = {"name": "tp_decode.4chip", "config": "brumby-14b-tp-decode",
       "traffic": "tp_layer_chain_scaled", "chips": 4, "why": "a test"}


@pytest.fixture
def run_tp4(tiny_bench, run_tiny):
    """The deferred tp_decode.4chip cell, added to the tiny benchmark."""
    root, spec = tiny_bench
    spec["workloads"].append(TP4)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return run_tiny


def test_tp4_sound_run_is_correct(run_tp4):
    out = run_tp4("tp_decode.4chip", seed=2**31 + 7)
    assert out["correct"], out
    assert out["checks"]["max_rel_err"]["value"] <= 2.0 ** -8
    assert out["checks"]["lane_misses"]["value"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0
    # every rank's every call of the last unit, and the kept units'
    assert out["checks"]["outputs_compared"]["value"] >= 4 * 6


def test_tp4_control_and_stale_are_not_correct(run_tp4, monkeypatch):
    out = run_tp4("tp_decode.4chip", seed=5, control=True)
    assert not out["correct"]
    monkeypatch.setattr(tpu_backend.TpuEngine, "_scatter_back",
                        lambda self, plan, y: None)
    assert not run_tp4("tp_decode.4chip", seed=7)["correct"]

