"""The expert-parallel MoE layer (accl_tpu.models.moe_ep) through the
driver on four CPU devices, against the plain float32 reference
(accl_tpu.models.moe_ep_reference) on seeded weights, at a small size:
hidden 64, 16 experts, top-6, expert width 32, two layers."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accl_tpu.models import moe_ep
from accl_tpu.models import moe_ep_reference as ref

P, T, L = 4, 24, 2
CFG = moe_ep.EPMoEConfig(hidden=64, n_experts=16, top_k=6, expert_width=32,
                         shared_width=64, scaling=2.446, eps=1e-5)
#: per token, ||y - y_ref|| / ||y_ref||.  The program keeps h, each
#: expert's hidden activation and the layer output in bf16 (2^-9
#: relative rounding each, f32 accumulation): 3.0e-3 to 4.8e-3 measured
#: at this size; the limit leaves 4x room
TOL = 2e-2


def _params(seed, bias=None):
    rng = np.random.default_rng(seed)
    H, F, Fs, E = CFG.hidden, CFG.expert_width, CFG.shared_width, \
        CFG.n_experts

    def g(*shape, fan_in=1, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * fan_in ** -0.5,
                           dtype)

    layers = []
    for _ in range(L):
        layers.append({
            "norm": (1 + 0.1 * g(H, dtype=jnp.float32)).astype(jnp.bfloat16),
            "router": g(E, H, fan_in=H, dtype=jnp.float32),
            "bias": (jnp.asarray(bias, jnp.float32) if bias is not None
                     else g(E, fan_in=100, dtype=jnp.float32)),
            "w1": g(E, H, F, fan_in=H), "w3": g(E, H, F, fan_in=H),
            "w2": g(E, F, H, fan_in=F), "s1": g(H, Fs, fan_in=H),
            "s3": g(H, Fs, fan_in=H), "s2": g(Fs, H, fan_in=Fs)})
    return layers


def _run(layers, xs):
    """Every rank's layer outputs, its stats, and the engine's."""
    from accl_tpu.backends.tpu import TpuWorld

    def fn(a, r):
        dev = world.engine.devices[r]
        layer = moe_ep.EPMoELayer(a, CFG, T)
        held = slice(layer.held.start, layer.held.stop)
        x = jax.device_put(xs[r], dev)
        outs = []
        for p in layers:
            p = {k: (v[held] if k in ("w1", "w3", "w2") else v)
                 for k, v in p.items()}
            x = layer(x, jax.device_put(p, dev))
            outs.append(np.asarray(x))
        stats = layer.stats()
        layer.close()
        return outs, stats

    with TpuWorld(P) as world:
        got = world.run(fn)
        engine = dict(world.engine.stats)
    return got, engine


def _inputs(seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((T, CFG.hidden)), jnp.bfloat16)
            for _ in range(P)]


def _max_rel_err(got, xs, layers):
    worst = 0.0
    for r in range(P):
        x = xs[r]
        for l, p in enumerate(layers):
            want = np.asarray(ref.moe_layer(x, p, CFG))
            y = got[r][0][l].astype(np.float32)
            err = (np.linalg.norm(y - want, axis=1)
                   / np.linalg.norm(want, axis=1))
            worst = max(worst, float(err.max()))
            x = jnp.asarray(got[r][0][l])   # the program's own input
    return worst


def test_layer_matches_reference_three_calls_a_layer():
    layers, xs = _params(1), _inputs(2)
    got, engine = _run(layers, xs)
    assert _max_rel_err(got, xs, layers) < TOL
    # alltoall of the counts, dispatch and combine: three gangs a layer,
    # every one assembled from prepared operands
    assert engine["lane_hlo"] == 3 * L
    assert engine["assemble_checked"] == 0
    # the layer writes its sends in the exchange's row form: no send is
    # reshaped at assembly
    assert engine["a2av_flat_operands"] == 0
    # dropless: every (token, slot) row lands on some rank, and comes back
    stats = [s for _o, s in got]
    assert all(s["calls"] == L for s in stats)
    assert sum(s["dispatch_recv_rows"] for s in stats) == P * T * 6 * L
    assert all(s["dispatch_offchip_bytes"]
               == s["dispatch_offchip_rows"] * CFG.hidden * 2
               for s in stats)


@partial(jax.jit, static_argnums=(0,))
def _flat_experts(cfg, rows, counts, p):
    """The expert layer as it ran on 1-D driver buffers: the received
    rows reshaped in, the output flattened out."""
    mm = partial(moe_ep._grouped_matmul, sizes=counts, interpret=True)
    xs = rows.reshape(-1, cfg.hidden)
    a = moe_ep._silu_glu(mm(xs, p["w1"]), mm(xs, p["w3"]))
    return mm(a.astype(jnp.bfloat16), p["w2"]).reshape(-1)


@partial(jax.jit, static_argnums=(0,))
def _flat_combine(cfg, back, order, gates, base):
    """The combine as it read a 1-D receive."""
    T = base.shape[0]
    y = back.reshape(T * cfg.top_k, cfg.hidden)[moe_ep._inverse(order)]
    y = y.reshape(T, cfg.top_k, cfg.hidden).astype(jnp.float32)
    return (base + jnp.sum(gates[..., None] * y, axis=1)).astype(
        jnp.bfloat16)


def _flat_layer_call(layer, x, p):
    """One layer call as the driver ran it on 1-D buffers, on `layer`'s
    buffers: every payload written and read flat."""
    cfg, a = layer.cfg, layer.accl
    kw = dict(from_fpga=True, to_fpga=True)
    rows, ecounts, pcounts, order, gates, _routed, local, _picks = \
        moe_ep._route(cfg, layer.nranks, x, p)
    layer.dispatch_send.set_dev_range(0, rows.reshape(-1))
    layer.ecount_send.set_dev_range(0, ecounts)
    layer.dispatch_count.set_dev_range(0, pcounts)
    a.alltoall(layer.ecount_send, layer.ecount_recv, layer.per_rank, **kw)
    a.alltoallv(layer.dispatch_send, layer.dispatch_recv,
                layer.dispatch_count, layer.dispatch_recv_count,
                row=cfg.hidden, **kw)
    out = _flat_experts(cfg, layer.dispatch_recv.flat,
                        layer.ecount_recv.dev, p)
    layer.combine_send.set_dev_range(0, out)
    a.alltoallv(layer.combine_send, layer.combine_recv,
                layer.dispatch_recv_count, layer.combine_recv_count,
                row=cfg.hidden, **kw)
    return _flat_combine(cfg, layer.combine_recv.flat, order, gates,
                         x.astype(jnp.float32) + local)


def test_row_form_layer_equals_the_flat_form():
    """The layer on row-form buffers gives the flat form's output bit
    for bit; the flat form's 1-D sends are each reshaped at assembly
    and counted, the row form's none."""
    from accl_tpu.backends.tpu import TpuWorld

    layers, xs = _params(9), _inputs(10)

    def fn(a, r):
        dev = world.engine.devices[r]
        rowf = moe_ep.EPMoELayer(a, CFG, T)
        flat = moe_ep.EPMoELayer(a, CFG, T)
        held = slice(rowf.held.start, rowf.held.stop)
        ps = [jax.device_put({k: (v[held] if k in ("w1", "w3", "w2")
                                  else v) for k, v in p.items()}, dev)
              for p in layers]
        x = y = jax.device_put(xs[r], dev)
        outs = []
        for p in ps:
            x = rowf(x, p)
            outs.append(np.asarray(x))
        a.barrier()
        mid = world.engine.stats["a2av_flat_operands"]
        a.barrier()
        for l, p in enumerate(ps):
            y = _flat_layer_call(flat, y, p)
            outs[l] = (outs[l], np.asarray(y))
        forms = (rowf.dispatch_recv.dev.shape, rowf.combine_recv.dev.shape)
        rowf.close()
        flat.close()
        return outs, mid, forms

    with TpuWorld(P) as world:
        got = world.run(fn)
        flat_ops = world.engine.stats["a2av_flat_operands"]
    # dropless: each token picks at most min(top-k, held) of a rank's
    # experts
    most = P * T * min(CFG.top_k, CFG.n_experts // P)
    cap = -(-most // moe_ep._TILE_ROWS) * moe_ep._TILE_ROWS
    for outs, mid, forms in got:
        assert mid == 0
        assert forms == ((cap, CFG.hidden), (T * CFG.top_k, CFG.hidden))
        for rowf, flat in outs:
            assert np.array_equal(rowf.view(np.uint16), flat.view(np.uint16))
    # every flat call's dispatch and combine send, on every rank
    assert flat_ops == 2 * P * L


def test_no_token_dropped_under_extreme_skew():
    # every token's six experts are 0-5: ranks 0 and 1 receive every
    # row of every rank, rank 0 two thirds of them, ranks 2 and 3 none
    bias = np.zeros(CFG.n_experts, np.float32)
    bias[:6] = 10.0
    layers, xs = _params(3, bias), _inputs(4)
    got, _ = _run(layers, xs)
    assert _max_rel_err(got, xs, layers) < TOL
    recv = [s["dispatch_recv_rows"] for _o, s in got]
    assert recv[0] == P * T * 4 * L and recv[1] == P * T * 2 * L
    assert recv[2] == recv[3] == 0


def _permuted_experts(nranks, rows, counts, p):
    """The expert layer as it was before its groups were (source, local
    expert): rows permuted into expert-major order, one ``ragged_dot``
    group per local expert, rows permuted back."""
    xs = rows.reshape(-1, CFG.hidden)
    held = counts.shape[0] // nranks
    ends = jnp.cumsum(counts)
    seg = jnp.searchsorted(ends, jnp.arange(xs.shape[0]), side="right")
    local = jnp.where(seg < counts.shape[0], seg % held, held)
    perm = jnp.argsort(local, stable=True)
    sizes = counts.reshape(nranks, held).sum(0)

    def mm(x, w):
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.bfloat16)

    xg = xs[perm]
    a = moe_ep._silu_glu(mm(xg, p["w1"]), mm(xg, p["w3"]))
    out = mm(a.astype(jnp.bfloat16), p["w2"])
    return out[moe_ep._inverse(perm)]


def _recv_counts(case, nranks, held, rng):
    """[nranks * held] rows received from source i for local expert e
    at ``i * held + e``; each source sends at most T * top_k rows."""
    sent = T * CFG.top_k
    c = np.zeros((nranks, held), np.int64)
    for i in range(nranks):
        c[i] = np.bincount(rng.integers(0, held, rng.integers(sent)),
                           minlength=held)
    if case == "empty_group":
        c[1, 2] = c[3, 0] = 0
    elif case == "zero_rows":
        c[:] = 0
    elif case == "one_source":
        c[np.arange(nranks) != nranks - 1] = 0
    elif case == "full_capacity":
        for i in range(nranks):
            c[i] = np.bincount(rng.integers(0, held, sent), minlength=held)
    return jnp.asarray(c.reshape(-1), jnp.int32)


@pytest.mark.parametrize("case", ["empty_group", "zero_rows", "one_source",
                                  "full_capacity", "one_rank"])
def test_experts_in_received_order_match_permuted_experts(case):
    nranks = 1 if case == "one_rank" else P
    held = CFG.n_experts // nranks
    rng = np.random.default_rng(7)
    counts = _recv_counts(case, nranks, held, rng)
    total = int(counts.sum())
    full = nranks * T * CFG.top_k   # every source's every row here
    tile = moe_ep._TILE_ROWS
    cap = -(-full // tile) * tile
    # rows past the received total hold noise here: none may leak out
    rows = jnp.asarray(rng.standard_normal((cap, CFG.hidden)),
                       jnp.bfloat16)
    p = {k: v[:held] for k, v in _params(8)[0].items()
         if k in ("w1", "w3", "w2")}
    got = np.asarray(moe_ep._experts(CFG, True, rows, counts, p))
    assert got.shape == (cap, CFG.hidden)   # row form in and out
    want = np.asarray(_permuted_experts(nranks, rows, counts, p))
    # the same products in f32, summed in another order: at most one
    # bf16 rounding step apart (rows past the total are unspecified)
    steps = np.abs(got[:total].view(np.int16).astype(np.int32)
                   - want[:total].view(np.int16).astype(np.int32))
    assert steps.max(initial=0) <= 1
    assert np.count_nonzero(steps) <= steps.size // 1000
    assert (total == full) == (case == "full_capacity")
    assert (total == 0) == (case == "zero_rows")


def test_select_is_the_layers_router():
    layers, xs = _params(5), _inputs(6)
    p = layers[0]
    sel = np.asarray(moe_ep.select(CFG, xs[0], p))
    want, _g, _s = ref.routing(xs[0], p, CFG)
    assert np.array_equal(np.sort(sel, 1), np.sort(np.asarray(want), 1))


def test_experts_must_divide_over_the_ranks():
    from accl_tpu.backends.tpu import TpuWorld

    with TpuWorld(3) as world:
        with pytest.raises(ValueError, match="divide"):
            world.run(lambda a, r: moe_ep.EPMoELayer(a, CFG, T))


def test_moonlight_defaults_unchanged():
    """The DeepSeek-V3 layer on its default shares (rank r holds experts
    4r..4r+3) gives what strided shares given explicitly (rank r holds
    r, r+4, r+8, r+12) give through the slot map, bit for bit: the new
    defaults leave Moonlight's layer as it was."""
    from accl_tpu.backends.tpu import TpuWorld

    from dataclasses import replace

    strided = replace(CFG, held=tuple(tuple(range(r, CFG.n_experts, P))
                                      for r in range(P)))
    assert CFG.held is None and CFG.zero_experts == 0
    assert CFG.router == "sigmoid_norm"
    layers, xs = _params(21), _inputs(22)

    def fn(a, r):
        dev = world.engine.devices[r]
        outs = []
        for cfg in (CFG, strided):
            layer = moe_ep.EPMoELayer(a, cfg, T)
            idx = np.asarray(list(layer.held))
            x = jax.device_put(xs[r], dev)
            ys = []
            for p in layers:
                p = {k: (v[idx] if k in ("w1", "w3", "w2") else v)
                     for k, v in p.items()}
                x = layer(x, jax.device_put(p, dev))
                ys.append(np.asarray(x))
            outs.append((ys, layer.stats()))
            layer.close()
        return outs

    with TpuWorld(P) as world:
        got = world.run(fn)
    for (base, sb), (other, so) in got:
        for yb, yo in zip(base, other):
            assert np.array_equal(yb.view(np.uint16), yo.view(np.uint16))
        assert sb["zero_rows"] == so["zero_rows"] == 0
        assert sb["absent_rows"] == so["absent_rows"] == 0
    assert sum(s["dispatch_recv_rows"] for (_y, s), _o in got) == \
        P * T * CFG.top_k * L
