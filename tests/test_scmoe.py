"""LongCat-Flash's ScMoE layer (accl_tpu.models.scmoe) through the
driver on CPU devices, against the plain float32 reference
(accl_tpu.models.scmoe_reference) on seeded weights, at a small size:
hidden 64, 16 routed experts of width 32 and 8 zero-compute ones, top-6
softmax routing scaled by 6, dense FFNs of width 96, two layers; and the
expert layer's held shares, zero-compute picks and decode-sized tiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accl_tpu.models import moe_ep, scmoe
from accl_tpu.models import scmoe_reference as ref

T, L = 16, 2
E, Z, K = 16, 8, 6
LONGCAT_TINY = {"hidden_size": 64, "n_routed_experts": E, "moe_topk": K,
                "expert_ffn_hidden_size": 32, "ffn_hidden_size": 96,
                "zero_expert_num": Z, "zero_expert_type": "identity",
                "routed_scaling_factor": 6, "rms_norm_eps": 1e-5}
#: per token, ||y - y_ref|| / ||y_ref - x||.  The program rounds u, each
#: FFN's hidden activation and the output to bf16 (2^-9 relative each,
#: f32 accumulation): 6.3e-3 to 8.6e-3 measured at this size over four
#: seeds, four ranks and one chip's share; 2.3x room
TOL = 2e-2


def _cfg(held=None):
    return scmoe.ScMoEConfig.from_hf(LONGCAT_TINY, held)


def _params(seed):
    """Every layer's parameters, every routed expert's weights among
    them; the router favours the zero-compute experts a little."""
    cfg = _cfg()
    H, F, D = cfg.moe.hidden, cfg.moe.expert_width, cfg.ffn_width
    rng = np.random.default_rng(seed)

    def g(*shape, fan_in=1, dtype=jnp.bfloat16):
        return np.asarray(rng.standard_normal(shape) * fan_in ** -0.5,
                          np.float32).astype(dtype)

    layers = []
    for _ in range(L):
        bias = 0.01 * rng.standard_normal(E + Z)
        bias[E:] += 0.01
        w = {"w1": g(E, H, F, fan_in=H), "w3": g(E, H, F, fan_in=H),
             "w2": g(E, F, H, fan_in=F)}
        layers.append({
            "norm": (1 + 0.1 * g(H, dtype=np.float32)).astype(jnp.bfloat16),
            "norm4": (1 + 0.1 * g(H, dtype=np.float32)).astype(
                jnp.bfloat16),
            "router": g(E + Z, H, fan_in=H, dtype=np.float32),
            "bias": bias.astype(np.float32),
            "ffn1_w1": g(H, D, fan_in=H), "ffn1_w3": g(H, D, fan_in=H),
            "ffn1_w2": g(D, H, fan_in=D), "ffn2_w1": g(H, D, fan_in=H),
            "ffn2_w3": g(H, D, fan_in=H), "ffn2_w2": g(D, H, fan_in=D),
            **w})
    return layers


def _share(p, held):
    idx = np.asarray(list(held))
    return {k: (v[idx] if k in ("w1", "w3", "w2") else v)
            for k, v in p.items()}


def _inputs(seed, n):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((T, 64)), jnp.bfloat16)
            for _ in range(n)]


def _run(cfg, layers, xs):
    """Every rank's layer outputs (each layer on the program's previous
    output) and its tally."""
    from accl_tpu.backends.tpu import TpuWorld

    def fn(a, r):
        dev = world.engine.devices[r]
        block = scmoe.ScMoEBlock(a, cfg, T)
        held = block.moe.held
        x = jax.device_put(xs[r], dev)
        outs = []
        for p in layers:
            x = block(x, jax.device_put(_share(p, held), dev))
            outs.append(np.asarray(x))
        stats = block.stats()
        block.close()
        return outs, stats, tuple(held)

    with TpuWorld(len(xs)) as world:
        got = world.run(fn)
        engine = dict(world.engine.stats)
    return got, engine


def _rel_err(y, want, x):
    y = np.asarray(y, np.float32)
    return (np.linalg.norm(y - want, axis=1)
            / np.linalg.norm(want - np.asarray(x, np.float32), axis=1))


def _picks(cfg, x, p, held):
    """(zero-compute picks, absent picks, held picks) of the program's
    router on `x`, counted with numpy."""
    sel = np.asarray(moe_ep.select(cfg.moe, x, p))
    zero = int((sel >= E).sum())
    mine = int(np.isin(sel, list(held)).sum())
    return zero, sel.size - zero - mine, mine


def _check(cfg, layers, xs, got, held_of):
    worst = 0.0
    counts = np.zeros(3, np.int64)
    for r, (outs, _stats, held) in enumerate(got):
        x = xs[r]
        for l, p in enumerate(layers):
            want = np.asarray(ref.layer(x, _share(p, held_of(held)), cfg,
                                        held_of(held)))
            worst = max(worst, float(_rel_err(outs[l], want, x).max()))
            counts += _picks(cfg, x, p, held_of(held))
            x = jnp.asarray(outs[l])   # the program's own input
    return worst, counts


def test_block_through_the_driver_on_four_ranks_matches_reference():
    """Four ranks hold the 16 routed experts, four each: every routed
    pick is dispatched to its rank, the zero-compute ones never."""
    cfg = _cfg()
    layers, xs = _params(1), _inputs(2, 4)
    got, engine = _run(cfg, layers, xs)
    # the union of the shares is every expert: the reference of the
    # whole layer
    worst, (zero, absent, _mine) = _check(cfg, layers, xs, got,
                                          lambda _h: range(E))
    assert worst < TOL
    assert engine["lane_hlo"] == 3 * L * 1   # three gangs a layer
    stats = [s for _o, s, _h in got]
    assert sum(s["zero_rows"] for s in stats) == zero > 0
    assert absent == 0 and all(s["absent_rows"] == 0 for s in stats)
    # every pick not served by identity went to some rank, once
    assert sum(s["dispatch_recv_rows"] for s in stats) == \
        4 * T * K * L - zero


@pytest.mark.parametrize("held", [(3, 11, 0, 7), (12, 13, 14, 15)])
def test_one_chip_share_matches_reference(held):
    """One chip holds 4 of the 16 routed experts: the picks of the other
    12 are left out, in the program and the reference alike."""
    cfg = _cfg((held,))
    layers, xs = _params(3), _inputs(4, 1)
    got, _engine = _run(cfg, layers, xs)
    worst, (zero, absent, mine) = _check(cfg, layers, xs, got,
                                         lambda h: h)
    assert got[0][2] == held
    assert worst < TOL
    s = got[0][1]
    assert (s["zero_rows"], s["absent_rows"]) == (zero, absent)
    assert zero > 0 and absent > 0
    # zero-compute and absent picks are never dispatched
    assert s["dispatch_recv_rows"] == mine == T * K * L - zero - absent
    assert s["dispatch_offchip_rows"] == s["combine_offchip_rows"] == 0


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four one-chip shares, with the dense path
    and the zero-compute experts counted once, are the uncut layer."""
    shares = [(0, 5, 9, 14), (1, 4, 10, 15), (2, 7, 8, 13), (3, 6, 11, 12)]
    layers, (x,) = _params(5)[:1], _inputs(6, 1)
    p = layers[0]
    cfg = _cfg()
    base, zero, _ = ref.parts(x, p, cfg, ())
    common = np.asarray(base + zero)
    whole = np.asarray(ref.layer(x, p, cfg, range(E)))
    # the reference's own shares add up exactly, up to f32 rounding
    ref_sum = sum(np.asarray(ref.parts(x, _share(p, h), cfg, h)[2])
                  for h in shares) + common
    assert np.abs(ref_sum - whole).max() <= 1e-5 * np.abs(whole).max()
    # and so do the program's, each share run alone on one chip
    ys = [_run(_cfg((h,)), [p], [x])[0][0][0][0] for h in shares]
    total = sum(np.asarray(y, np.float32) for y in ys) - 3 * common
    assert _rel_err(total, whole, x).max() < 2 * TOL


# -- the grouped matmul's tiles -------------------------------------------
def _group_rows(rng, counts, cap, K):
    rows = jnp.asarray(rng.standard_normal((cap, K)), jnp.bfloat16)
    return rows, jnp.asarray(counts, jnp.int32)


def test_tiles_follow_the_shapes():
    tiles = moe_ep._tiles
    # Moonlight's prefill keeps 512-row tiles and whole weights
    assert tiles(196608, 2048, 1408, 64) == (512, 1408)
    assert tiles(196608, 1408, 2048, 64) == (512, 2048)
    # a LongCat decode step: 256 tokens, 8 held experts on one chip
    assert tiles(2048, 6144, 2048, 8) == (128, 2048)
    assert tiles(2048, 2048, 6144, 8) == (128, 6144)
    # a whole [6144, 2048] weight and 512-row tiles overflow the budget:
    # the weight is blocked along N
    tm, tn = tiles(8192, 6144, 2048, 8)
    assert (tm, tn) == (512, 1024)
    assert moe_ep._vmem_bytes(tm, 6144, tn) <= moe_ep._VMEM_BYTES
    assert moe_ep._vmem_bytes(512, 6144, 2048) > moe_ep._VMEM_BYTES


def test_decode_tile_equals_the_512_row_tile():
    """A decode capacity (2048 rows in 8 groups: 128-row tiles) gives
    the 512-row tile's output bit for bit over the received rows."""
    rng = np.random.default_rng(11)
    counts = np.array([3, 0, 41, 7, 1, 0, 130, 22])
    total = int(counts.sum())
    rows, sizes = _group_rows(rng, counts, 2048, 64)
    w = jnp.asarray(rng.standard_normal((8, 64, 256)) / 8, jnp.bfloat16)
    assert moe_ep._tiles(2048, 64, 256, 8)[0] == 128
    assert moe_ep._tiles(8192, 64, 256, 8)[0] == 512
    small = moe_ep._grouped_matmul(rows, w, sizes, interpret=True)
    wide = jnp.concatenate([rows, jnp.zeros((6144, 64), jnp.bfloat16)])
    big = moe_ep._grouped_matmul(wide, w, sizes, interpret=True)
    assert np.array_equal(np.asarray(small[:total]).view(np.uint16),
                          np.asarray(big[:total]).view(np.uint16))
    want = np.concatenate([
        np.asarray(rows[a:b], np.float32) @ np.asarray(w[g], np.float32)
        for g, (a, b) in enumerate(zip(np.r_[0, np.cumsum(counts)[:-1]],
                                       np.cumsum(counts)))])
    assert np.allclose(np.asarray(small[:total], np.float32), want,
                       rtol=1e-2, atol=1e-2)


def test_weight_blocked_along_n_equals_the_whole_weight(monkeypatch):
    rng = np.random.default_rng(12)
    counts = np.array([5, 300, 0, 17])
    total = int(counts.sum())
    rows, sizes = _group_rows(rng, counts, 1024, 128)
    w = jnp.asarray(rng.standard_normal((4, 128, 512)) / 11, jnp.bfloat16)
    whole = moe_ep._grouped_matmul(rows, w, sizes, interpret=True)
    # a budget that holds a quarter of the weight's columns
    monkeypatch.setattr(moe_ep, "_VMEM_BYTES",
                        moe_ep._vmem_bytes(128, 128, 128))
    assert moe_ep._tiles(1024, 128, 512, 4) == (128, 128)
    blocked = moe_ep._grouped_matmul(rows, w, sizes, interpret=True)
    assert np.array_equal(np.asarray(whole[:total]).view(np.uint16),
                          np.asarray(blocked[:total]).view(np.uint16))


# -- the configuration ----------------------------------------------------
def test_from_hf_reads_longcat_and_deepseek_keys():
    m = _cfg().moe
    assert (m.n_experts, m.zero_experts, m.top_k, m.expert_width,
            m.shared_width, m.scaling, m.router) == \
        (E, Z, K, 32, 0, 6.0, "softmax")
    assert m.router_width == E + Z and m.held is None
    ds = moe_ep.EPMoEConfig.from_hf({
        "hidden_size": 2048, "n_routed_experts": 64,
        "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
        "n_shared_experts": 2, "routed_scaling_factor": 2.446,
        "rms_norm_eps": 1e-5})
    assert (ds.router, ds.zero_experts, ds.held, ds.shared_width) == \
        ("sigmoid_norm", 0, None, 2816)
    assert ds.all_routed(4) and not m.all_routed(4)
    with pytest.raises(ValueError, match="zero_expert_type"):
        scmoe.ScMoEConfig.from_hf(dict(LONGCAT_TINY,
                                       zero_expert_type="copy"))


@pytest.mark.parametrize("held", [((0, 1), (1, 2)), ((0, 1), (2,)),
                                  ((0, 16),), ()])
def test_held_shares_must_be_distinct_routed_ids(held):
    cfg = _cfg(held)
    with pytest.raises(ValueError, match="held"):
        cfg.moe.shares(max(len(held), 1))

