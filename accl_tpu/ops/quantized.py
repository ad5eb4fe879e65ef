"""Int8 block-scaled quantized collectives — the wire-compression
algebra extended one tier below fp16.

The reference's hp_compression plugin stops at fp32<->fp16 (2:1 on the
wire, hp_compression.cpp:70-144).  On TPU the same role generalizes to
4:1: payloads cross the ICI ring as int8 with one fp32 scale per
`block` elements (symmetric absmax scaling), accumulation stays fp32 —
the EQuARX-style quantized allreduce of PAPERS.md.  Everything here is
jnp-level inside shard_map: quantization is elementwise + a small
reduction, exactly what XLA fuses into the ppermute pipeline on its own
(no Pallas needed — don't hand-schedule what the compiler already
does).

Error model: one symmetric absmax quantization rounds to within
scale/2 = absmax/254 per element.  The ring reduce-scatter requantizes
the running partial each hop (P-1 hops), so worst-case error grows
linearly in P — the same bias the reference's fp16 wire accumulates
over its fused recv-reduce-send rings, two tiers sharper.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


DEFAULT_BLOCK = 256


def _blocks(x, block: int):
    n = x.shape[0]
    rows = -(-n // block)
    pad = rows * block - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros(pad, x.dtype)])
    return x.reshape(rows, block), n


def quantize_blockwise(x, block: int = DEFAULT_BLOCK, key=None):
    """Flat fp array -> (q int8 [rows, block], scale f32 [rows, 1], n).

    Symmetric per-block absmax scaling; all-zero blocks get scale 1 so
    dequantization is exact for them.  With ``key`` (a jax PRNG key)
    rounding is STOCHASTIC — floor(r + u), u ~ U[0,1) — the same
    bias-breaking role the Pallas compress lanes' on-core PRNG plays
    (ops/compression.py stochastic_round); callers fold the ring
    hop/rank into the key so hops decorrelate."""
    x2, n = _blocks(x.astype(jnp.float32), block)
    amax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    r = x2 / scale
    if key is not None:
        import jax

        u = jax.random.uniform(key, r.shape, jnp.float32)
        rounded = jnp.floor(r + u)
    else:
        rounded = jnp.round(r)
    q = jnp.clip(rounded, -127, 127).astype(jnp.int8)
    return q, scale, n


def dequantize_blockwise(q, scale, n: int):
    """Inverse of :func:`quantize_blockwise` -> flat f32 [n]."""
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


def _hop_key(seed: int, axis: str, hop):
    """PRNG key decorrelated per (seed, rank, hop) for stochastic
    rounding inside the ring loop."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             lax.axis_index(axis))
    return jax.random.fold_in(key, hop)


def _ring_reduce_scatter_q(x, axis: str, block: int,
                           error_feedback: bool = False,
                           stochastic: bool = False, seed: int = 0):
    """Quantized ring reduce-scatter returning the WIRE-FORM carry
    (q, scale, n) of this member's reduced chunk — so the all-reduce can
    feed it straight into the gather phase without a dequant/requant
    round at the seam.

    ``error_feedback`` (EQuARX, arxiv 2506.17615): each hop's
    requantization error is carried into the NEXT hop's quantization
    input instead of being dropped, so per-hop bias dithers out instead
    of accumulating linearly in P.  ``stochastic`` rounds with PRNG
    bits per (rank, hop) — the jnp twin of the Pallas compress lanes'
    on-core stochastic_round."""
    size = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    if x.shape[0] % size != 0:
        raise ValueError(
            f"quantized ring reduce-scatter needs x.shape[0] ({x.shape[0]}) "
            f"divisible by the '{axis}' axis size ({size}); pad the input "
            "(sync_gradients pads via _pad_to_multiple)")
    n = x.shape[0] // size
    chunks = x.astype(jnp.float32).reshape(size, n)

    x0 = chunks[(idx - 1) % size]
    q0, s0, _ = quantize_blockwise(
        x0, block, key=_hop_key(seed, axis, 0) if stochastic else None)
    err0 = (x0 - dequantize_blockwise(q0, s0, n)) if error_feedback \
        else jnp.zeros((n,), jnp.float32)
    fwd = [(i, (i + 1) % size) for i in range(size)]

    def step(s, carry):
        q, sc, err = carry
        q = lax.ppermute(q, axis, fwd)
        sc = lax.ppermute(sc, axis, fwd)
        acc = dequantize_blockwise(q, sc, n) + chunks[(idx - 2 - s) % size]
        if error_feedback:
            acc = acc + err
        qn, scn, _ = quantize_blockwise(
            acc, block,
            key=_hop_key(seed, axis, s + 1) if stochastic else None)
        if error_feedback:
            err = acc - dequantize_blockwise(qn, scn, n)
        return qn, scn, err

    q, sc, _err = lax.fori_loop(0, size - 1, step, (q0, s0, err0))
    return q, sc, n


def _ring_all_gather_q(q, sc, n: int, axis: str):
    """Ring all-gather of an already-quantized (q, scale) pair -> flat
    [P * n] f32 (rank-major); contributions are relayed in wire form and
    dequantized once at the end."""
    size = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    fwd = [(i, (i + 1) % size) for i in range(size)]

    out_q = jnp.zeros((size,) + q.shape, q.dtype).at[idx].set(q)
    out_s = jnp.zeros((size,) + sc.shape, sc.dtype).at[idx].set(sc)

    def step(s, carry):
        oq, os, cq, cs = carry
        cq = lax.ppermute(cq, axis, fwd)
        cs = lax.ppermute(cs, axis, fwd)
        origin = (idx - 1 - s) % size
        return oq.at[origin].set(cq), os.at[origin].set(cs), cq, cs

    out_q, out_s, _, _ = lax.fori_loop(0, size - 1, step,
                                       (out_q, out_s, q, sc))
    deq = out_q.astype(jnp.float32) * out_s  # [P, rows, block]
    return deq.reshape(size, -1)[:, :n].reshape(-1)


def quantized_ring_reduce_scatter(x, axis: str = "rank",
                                  block: int = DEFAULT_BLOCK,
                                  error_feedback: bool = False,
                                  stochastic: bool = False,
                                  seed: int = 0):
    """Ring reduce-scatter whose wire traffic is int8 + per-block scales.

    `x`: flat [P * n] per member -> this member's reduced chunk [n] f32.
    Each hop sends the quantized running partial one hop forward; the
    receiver dequantizes, folds its own chunk in fp32, and requantizes —
    the fused recv-reduce-send of the firmware's ring (fw :1782-1850)
    with a 4:1 wire format.  ``error_feedback``/``stochastic``: see
    :func:`_ring_reduce_scatter_q`."""
    q, sc, n = _ring_reduce_scatter_q(x, axis, block, error_feedback,
                                      stochastic, seed)
    return dequantize_blockwise(q, sc, n)


def quantized_ring_all_gather(x, axis: str = "rank",
                              block: int = DEFAULT_BLOCK,
                              stochastic: bool = False, seed: int = 0):
    """Ring all-gather whose wire traffic is int8 + per-block scales.

    `x`: flat [n] f32 per member -> [P * n] f32 (rank-major).  Each
    member's contribution is quantized ONCE and relayed; the error is a
    single round-trip regardless of P."""
    q, sc, _ = quantize_blockwise(
        x.astype(jnp.float32), block,
        key=_hop_key(seed, axis, 0) if stochastic else None)
    return _ring_all_gather_q(q, sc, x.shape[0], axis)


def quantized_all_reduce(x, axis: str = "rank",
                         block: int = DEFAULT_BLOCK,
                         error_feedback: bool = False,
                         stochastic: bool = False, seed: int = 0):
    """Segmented ring allreduce with int8 wire traffic: quantized ring
    reduce-scatter + quantized ring all-gather (the fused schedule of fw
    :1888-2071 at 4:1 wire width).  `x`: flat [P * n] -> [P * n] f32.
    The reduce-scatter's wire-form carry feeds the gather directly — no
    dequant/requant round at the seam."""
    q, sc, n = _ring_reduce_scatter_q(x, axis, block, error_feedback,
                                      stochastic, seed)
    return _ring_all_gather_q(q, sc, n, axis)
