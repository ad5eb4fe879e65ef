"""Ring attention: sequence/context parallelism over the ICI ring.

SURVEY §5 notes the reference's ring schedules with fused
recv-reduce-send are "precisely ring attention's comm pattern"; this
module builds that pattern as a first-class feature.  Each member holds
a sequence shard of Q/K/V; K/V blocks rotate around the ring (ppermute —
the eager ring relay, fw :1404-1502) while a streaming-softmax
accumulator folds each arriving block into the local output — the
fused_recv_reduce of the firmware (fw :718) with the log-sum-exp
update playing the reduction operator.

Causal masking is blockwise: a K/V block strictly in the future
contributes nothing, the diagonal block takes a triangular mask, past
blocks attend fully.

Call inside shard_map with q/k/v sharded on the sequence axis:
    out = ring_attention(q, k, v, axis="sp", causal=True)
    q,k,v: [B, T_local, H, D] → out: [B, T_local, H, D]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.platform import pallas_interpret

NEG_INF = -1e30


def _flash_defaults(q):
    """Backend-resolved defaults for the SP paths' flash usage: whether
    this process should run the Pallas kernels at all (TPU only — the
    HLO interpreter can't run inside shard_map with check_vma=True), and
    the MXU input format (16-bit activations keep their format, f32
    stays exact)."""
    on_tpu = not pallas_interpret()
    mxu_dt = (q.dtype if q.dtype in (jnp.bfloat16, jnp.float16)
              else jnp.float32)
    return on_tpu, mxu_dt


def _block_attn(q, k, v, bias):
    """Scores + masked streaming-softmax contributions for one K/V block.

    q: [B, Tq, H, D], k/v: [B, Tk, H, D], bias: [Tq, Tk] additive mask.
    Returns (m_blk [B,H,Tq], p [B,H,Tq,Tk], pv [B,Tq,H,D])."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    # [B, H, Tq, Tk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + bias[None, None, :, :]
    m_blk = jnp.max(s, axis=-1)
    p = jnp.exp(s - m_blk[..., None])
    # zero fully-masked rows (m_blk == NEG_INF -> exp(0)=1 garbage)
    dead = m_blk <= NEG_INF / 2
    p = jnp.where(dead[..., None], 0.0, p)
    m_blk = jnp.where(dead, NEG_INF, m_blk)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    return m_blk, p, pv


def zigzag_indices(T: int, P: int):
    """Global sequence permutation for the zigzag causal layout: rank i
    holds chunk i and its mirror chunk 2P-1-i (each T/(2P) long), so
    every rank's total causal work — and, with the zigzag ring schedule,
    its work on EVERY hop — is identical.  The rank-major contiguous
    layout gives rank 0 one live shard and rank P-1 all P, so the ring's
    lockstep hops wait on the heaviest rank; zigzag removes that 2x
    wall-clock loss.

    Returns an int32 index array `perm` such that `x[:, perm]` reorders
    a [B, T, ...] global sequence into zigzag order (shard the result on
    the sequence axis as usual).  Apply the inverse
    (`zigzag_indices_inverse`) to outputs to return to natural order.
    """
    if T % (2 * P) != 0:
        raise ValueError(f"T={T} not divisible by 2*P={2 * P}")
    C = T // (2 * P)
    import numpy as _np

    chunks = []
    for i in range(P):
        chunks.append(_np.arange(i * C, (i + 1) * C))
        j = 2 * P - 1 - i
        chunks.append(_np.arange(j * C, (j + 1) * C))
    return jnp.asarray(_np.concatenate(chunks), jnp.int32)


def zigzag_indices_inverse(T: int, P: int):
    """Inverse of :func:`zigzag_indices` (natural <- zigzag)."""
    import numpy as _np

    perm = _np.asarray(zigzag_indices(T, P))
    inv = _np.empty_like(perm)
    inv[perm] = _np.arange(T, dtype=perm.dtype)
    return jnp.asarray(inv, jnp.int32)


def ring_attention(q, k, v, axis: str = "sp", causal: bool = False,
                   impl: str | None = None, schedule: str = "contiguous",
                   flash_opts: dict | None = None,
                   window: int | None = None):
    """Exact attention over the full (ring-distributed) sequence.

    Per-member shapes [B, T_local, H, D]; the global sequence is the
    rank-major concatenation of shards.  Numerics accumulate in fp32
    regardless of input dtype.

    `impl="flash"` computes each hop's local block with the Pallas flash
    kernel (no [Tl, Tl] score matrix in HBM; MXU-format matmuls follow
    the input dtype) and folds shards by log-sum-exp weighting;
    `impl="dense"` is the jnp reference path.  Default: flash on TPU,
    dense on the CPU rung (the Pallas HLO interpreter can't run inside
    shard_map with check_vma=True — jax#vma dynamic_slice limitation;
    flash-ring CPU tests pass check_vma=False explicitly).

    `schedule="zigzag"` (causal only) expects the global sequence
    permuted by :func:`zigzag_indices` before sharding, and balances the
    causal work exactly across ranks on every hop (each rank computes
    precisely two live half-chunk pairs per hop); the output is in the
    same zigzag order.  `schedule="contiguous"` is the natural layout.

    `flash_opts` forwards static schedule options to the per-hop flash
    kernel (e.g. ``{"q_tiles": 2, "fuse_denom": True}``) so distributed
    callers can run the chip-tuned schedule; ignored by the dense impl.

    `window` (causal + contiguous only, window <= T_local) runs
    SLIDING-WINDOW attention under sequence parallelism: each query's
    visible band fits in its own shard plus the previous one, so the
    schedule is the local windowed block + ONE neighbor hop instead of
    a P-hop ring (see :func:`_ring_attention_windowed`).
    """
    if schedule not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring_attention schedule {schedule!r}")
    if impl is None:
        impl = "flash" if _flash_defaults(q)[0] else "dense"
    if k.shape[2] != q.shape[2]:
        # grouped-query K/V ([B, Tl, G, D], G dividing H): the flash
        # hops consume the grouped layout in place — the ring then
        # rotates H/G-times-smaller shards, a direct ICI-bandwidth win.
        # The dense reference path expands per q head here instead.
        if q.shape[2] % k.shape[2] != 0:
            raise ValueError(
                f"K/V heads {k.shape[2]} must divide q heads "
                f"{q.shape[2]} for GQA")
        if impl == "dense":
            k, v = expand_gqa_kv(k, v, q.shape[2])
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (a sliding "
                             "window is a trailing-context mask)")
        if schedule != "contiguous":
            raise ValueError("window composes with the contiguous "
                             "schedule only (the zigzag layout's split "
                             "chunks break the one-neighbor-hop bound)")
        Tl = q.shape[1]
        if window < 1 or window > Tl:
            raise ValueError(
                f"window={window} must be in [1, T_local={Tl}]: larger "
                "windows span more than one neighbor shard (shard the "
                "sequence into fewer, longer pieces)")
        return _ring_attention_windowed(q, k, v, axis, window, impl,
                                        flash_opts=flash_opts)
    if schedule == "zigzag":
        if not causal:
            raise ValueError("zigzag schedule only makes sense for causal "
                             "attention (non-causal hops are already "
                             "balanced)")
        if impl == "flash":
            return _ring_attention_flash_zigzag(q, k, v, axis,
                                                flash_opts=flash_opts)
        if impl != "dense":
            raise ValueError(f"unknown ring_attention impl {impl!r}")
        return _ring_attention_dense_zigzag(q, k, v, axis)
    if impl == "flash":
        return _ring_attention_flash(q, k, v, axis, causal,
                                     flash_opts=flash_opts)
    if impl != "dense":
        raise ValueError(f"unknown ring_attention impl {impl!r}")
    if causal:
        Tl = q.shape[1]

        def bias_fn(idx, src):
            qpos = idx * Tl + lax.broadcasted_iota(jnp.int32, (Tl, Tl), 0)
            kpos = src * Tl + lax.broadcasted_iota(jnp.int32, (Tl, Tl), 1)
            return jnp.where(qpos >= kpos, 0.0, NEG_INF).astype(jnp.float32)
    else:
        bias_fn = None
    return _dense_ring_loop(q, k, v, axis, bias_fn)


def _dense_ring_loop(q, k, v, axis: str, bias_fn):
    """The dense (jnp) ring schedule shared by the contiguous and zigzag
    layouts: rotate K/V around the ring, fold each arriving shard with a
    streaming-softmax accumulator.  `bias_fn(idx, src) -> [Tl, Tl]`
    computes the additive causal mask for the shard that originated at
    rank `src` (None = unmasked)."""
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, D = q.shape
    perm = [(i, (i + 1) % P) for i in range(P)]

    qf = q.astype(jnp.float32)

    def step(s, carry):
        o, m, lsum, kc, vc = carry
        # current block originated at rank (idx - s) mod P
        src = (idx - s) % P
        bias = (bias_fn(idx, src) if bias_fn is not None
                else jnp.zeros((Tl, Tl), jnp.float32))
        m_blk, p, pv = _block_attn(qf, kc.astype(jnp.float32),
                                   vc.astype(jnp.float32), bias)
        m_new = jnp.maximum(m, m_blk)
        # guard the all-dead case (exp(NEG_INF - NEG_INF) = 1)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        beta = jnp.where(m_blk <= NEG_INF / 2, 0.0, jnp.exp(m_blk - m_new))
        l_new = lsum * alpha + jnp.sum(p, axis=-1) * beta
        o_new = (o * alpha.transpose(0, 2, 1)[..., None]
                 + pv * beta.transpose(0, 2, 1)[..., None])
        # rotate K/V one hop (the ring relay)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return o_new, m_new, l_new, kc, vc

    # accumulators must carry the same device-variance (vma) as the
    # values the loop produces — derive their zeros from q/k/v so the
    # fori_loop carry types match under any mesh composition
    zkv = (jnp.sum(k).astype(jnp.float32)
           + jnp.sum(v).astype(jnp.float32)) * 0.0
    o0 = qf * 0.0 + zkv
    zt = jnp.transpose(jnp.sum(o0, axis=-1), (0, 2, 1))  # [B, H, Tl] zeros
    m0 = zt + NEG_INF
    l0 = zt
    o, m, lsum, _, _ = lax.fori_loop(0, P, step, (o0, m0, l0, k, v))
    lsum = jnp.maximum(lsum, 1e-30)
    out = o / lsum.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _lse_merge(o, lse, o_i, lse_i, _NI=NEG_INF):
    """lse-weighted merge of normalized partial attentions (exact; dead
    partials carry lse = -inf and weight 0).  o/o_i: [B, T, H, D] (o is
    the fp32 running carry), lse/lse_i: [B, H, T].  Returns (o', lse')."""
    m_new = jnp.maximum(lse, lse_i)
    safe = jnp.where(m_new <= _NI / 2, 0.0, m_new)
    w_r = jnp.where(lse <= _NI / 2, 0.0, jnp.exp(lse - safe))
    w_i = jnp.where(lse_i <= _NI / 2, 0.0, jnp.exp(lse_i - safe))
    # normal-range epsilon: 1e-38 is subnormal f32 and flushes to zero
    # under FTZ, making the both-dead case 0/0 = NaN
    tot = jnp.maximum(w_r + w_i, 1e-30)
    wr4 = (w_r / tot).transpose(0, 2, 1)[..., None]  # [B, T, H, 1]
    wi4 = (w_i / tot).transpose(0, 2, 1)[..., None]
    o_new = o * wr4 + o_i.astype(jnp.float32) * wi4
    lse_new = jnp.where((w_r + w_i) == 0.0, jnp.full_like(m_new, _NI),
                        safe + jnp.log(tot))
    return o_new, lse_new


def _ring_attention_dense_zigzag(q, k, v, axis: str):
    """Dense (jnp) zigzag schedule: the shared ring loop with the causal
    bias computed from the zigzag GLOBAL positions of the local rows
    (chunk idx and its mirror 2P-1-idx) instead of a contiguous
    offset."""
    P = lax.axis_size(axis)
    Tl = q.shape[1]
    if Tl % 2 != 0:
        raise ValueError(f"zigzag needs an even local length, got {Tl}")
    C = Tl // 2

    def positions(r):
        ar = lax.iota(jnp.int32, C)
        return jnp.concatenate([r * C + ar, (2 * P - 1 - r) * C + ar])

    def bias_fn(idx, src):
        qpos, kpos = positions(idx), positions(src)
        return jnp.where(qpos[:, None] >= kpos[None, :], 0.0,
                         NEG_INF).astype(jnp.float32)

    return _dense_ring_loop(q, k, v, axis, bias_fn)


def _ring_attention_flash_zigzag(q, k, v, axis: str,
                                 flash_opts: dict | None = None):
    """Flash-backed zigzag causal ring schedule — exact per-hop load
    balance.

    Each rank's local row holds chunks (idx, 2P-1-idx), each C = Tl/2
    long.  With arriving chunks (a, 2P-1-a), a = (idx - s) mod P, the
    chunk-pair liveness works out to EXACTLY two live half-chunk flash
    calls per rank per hop (three half-size ones on the diagonal hop,
    simultaneously for all ranks):

      (qh, kl): always live, full          [kl = chunk a, qh = 2P-1-idx]
      a < idx:  (ql, kl) full              [ql's past]
      a == idx: (ql, kl) + (qh, kh) causal [the diagonal hop, s = 0]
      a > idx:  (qh, kh) full              [kh = 2P-1-a <= 2P-1-idx]
      (ql, kh): never live                 [kh >= P > ql's chunk]

    so the lockstep ppermute never waits on a heavier neighbor — the
    contiguous causal schedule degrades to the heaviest rank (P live
    shards) while the average is P/2."""
    from ..ops.flash import NEG_INF as _NI
    from ..ops.flash import flash_attention_lse

    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, D = q.shape
    if Tl % 2 != 0:
        raise ValueError(f"zigzag needs an even local length, got {Tl}")
    C = Tl // 2
    perm = [(i, (i + 1) % P) for i in range(P)]
    on_tpu, mxu_dt = _flash_defaults(q)
    interpret = not on_tpu

    ql, qh = q[:, :C], q[:, C:]

    def flash(qx, kx, vx, causal):
        return flash_attention_lse(qx, kx, vx, causal=causal,
                                   interpret=interpret, mxu_dtype=mxu_dt,
                                   **(flash_opts or {}))

    def dead(kx, vx):
        # zeros carrying the same device-variance as the live branches
        zkv = (jnp.sum(kx).astype(jnp.float32)
               + jnp.sum(vx).astype(jnp.float32)) * 0.0
        o_z = (ql.astype(jnp.float32) * 0.0 + zkv).astype(q.dtype)
        lse_z = jnp.transpose(
            jnp.sum(o_z.astype(jnp.float32), axis=-1), (0, 2, 1)) + _NI
        return o_z, lse_z

    def step(s, carry):
        o_lo, lse_lo, o_hi, lse_hi, kc, vc = carry
        src = (idx - s) % P
        kl, kh = kc[:, :C], kc[:, C:]
        vl, vh = vc[:, :C], vc[:, C:]

        # always-live pair: qh attends the arriving low chunk fully
        o_hb, lse_hb = flash(qh, kl, vl, causal=False)

        # branch on the arriving low chunk's position vs ours
        def past(_):   # a < idx: ql's past arrived
            o1, s1 = flash(ql, kl, vl, causal=False)
            o2, s2 = dead(kh, vh)
            return o1, s1, o2, s2

        def diag(_):   # a == idx: both diagonals (hop 0)
            o1, s1 = flash(ql, kl, vl, causal=True)
            o2, s2 = flash(qh, kh, vh, causal=True)
            return o1, s1, o2, s2

        def future(_):  # a > idx: qh's mirror-past arrived
            o1, s1 = dead(kl, vl)
            o2, s2 = flash(qh, kh, vh, causal=False)
            return o1, s1, o2, s2

        branch = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
        o_li, lse_li, o_he, lse_he = lax.switch(
            branch, (past, diag, future), None)

        o_lo, lse_lo = _lse_merge(o_lo, lse_lo, o_li, lse_li, _NI)
        o_hi, lse_hi = _lse_merge(o_hi, lse_hi, o_hb, lse_hb, _NI)
        o_hi, lse_hi = _lse_merge(o_hi, lse_hi, o_he, lse_he, _NI)

        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return o_lo, lse_lo, o_hi, lse_hi, kc, vc

    zkv = (jnp.sum(k).astype(jnp.float32)
           + jnp.sum(v).astype(jnp.float32)) * 0.0
    o0 = ql.astype(jnp.float32) * 0.0 + zkv
    lse0 = jnp.transpose(jnp.sum(o0, axis=-1), (0, 2, 1)) + NEG_INF
    o_lo, _sl, o_hi, _sh, _, _ = lax.fori_loop(
        0, P, step, (o0, lse0, o0, lse0, k, v))
    return jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype)




def _banded_cross_lse(q, kk, vv, offset: int, window: int, live):
    """lse-emitting dense attention of a q shard against ONE K/V shard
    under a trailing window, in relative coordinates: q row i sits
    `offset + i - j` positions after k row j; a cell contributes iff
    0 <= offset + i - j < window (the >= 0 half IS causality).  `live`
    is a traced bool gating the whole block (rank 0 has no previous
    shard).  Returns (o [B, T, H, D] normalized, lse [B, H, T] natural
    log) with dead rows at lse = -inf / o = 0 — the _lse_merge
    contract, so partial blocks fold exactly."""
    B, T, H, D = q.shape
    Tk = kk.shape[1]
    if kk.shape[2] != H:
        kk, vv = expand_gqa_kv(kk, vv, H)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    d = (offset + lax.broadcasted_iota(jnp.int32, (T, Tk), 0)
         - lax.broadcasted_iota(jnp.int32, (T, Tk), 1))
    keep = (d >= 0) & (d < window) & live
    s = jnp.where(keep[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    shift = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - shift)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    lsum = jnp.sum(p, axis=-1, keepdims=True)
    # epsilon must be a NORMAL f32: 1e-38 is subnormal and flushes to
    # zero under FTZ, turning the dead-row guard into 0/0 = NaN
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(lsum, 1e-30),
                     vv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    lse = jnp.where(lsum[..., 0] == 0.0, NEG_INF,
                    shift[..., 0] + jnp.log(jnp.maximum(lsum[..., 0],
                                                        1e-30)))
    return out, lse  # o fp32, lse [B, H, T]


def _ring_attention_windowed(q, k, v, axis: str, window: int,
                             impl: str, flash_opts: dict | None = None):
    """Sliding-window attention under sequence parallelism (contiguous
    shards, causal, window <= T_local): every query's visible band
    lies within its OWN shard plus the previous one, so the full ring
    collapses to the local block + ONE neighbor hop — O(1) in the ring
    size where the unwindowed ring is O(P) (the Mistral-style
    long-context composition the r4 build rejected outright).

    Local block: the shard's own causal window attention (the flash
    grid schedule's bounded-liveness path on TPU).  Boundary block:
    a banded dense cross against the previous shard's K/V (one block
    per rank — it cannot dominate at scale).  Exact merge by lse."""
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, D = q.shape

    if impl == "flash":
        from ..ops.flash import flash_attention_lse

        on_tpu, mxu_dt = _flash_defaults(q)
        opts = dict(flash_opts or {})
        opts.setdefault("interpret", not on_tpu)
        opts.setdefault("mxu_dtype", mxu_dt)
        o_loc, lse_loc = flash_attention_lse(q, k, v, causal=True,
                                             window=window, **opts)
        o_loc = o_loc.astype(jnp.float32)
    elif impl == "dense":
        # local block through the SAME banded helper (offset 0: the
        # d >= 0 arm is exactly the causal mask)
        o_loc, lse_loc = _banded_cross_lse(q, k, v, 0, window,
                                           jnp.bool_(True))
    else:
        raise ValueError(f"unknown ring_attention impl {impl!r}")

    Wn = window - 1  # boundary band width (window=1: self-only)
    if Wn == 0:
        return o_loc.astype(q.dtype)

    # ONE hop, STATICALLY SLICED to the live band: only the previous
    # shard's last Wn rows are visible to anyone here, and only this
    # shard's first Wn queries can see them — the hop moves
    # O(window) K/V bytes and the cross scores O(window^2) cells, not
    # O(Tl^2) (at Tl >> window the full-shard version would dominate
    # exactly where the windowed path is meant to win)
    perm = [(i, (i + 1) % P) for i in range(P)]
    ktail = lax.ppermute(k[:, Tl - Wn:], axis, perm)
    vtail = lax.ppermute(v[:, Tl - Wn:], axis, perm)
    # tail row j' is global position (prev shard) Tl - Wn + j', so a
    # local query i sits i + Wn - j' positions after it
    o_bs, lse_bs = _banded_cross_lse(q[:, :Wn], ktail, vtail, Wn,
                                     window, idx > 0)
    H_q = q.shape[2]
    o_b = jnp.zeros((B, Tl, H_q, D), jnp.float32).at[:, :Wn].set(o_bs)
    lse_b = jnp.full((B, H_q, Tl), NEG_INF,
                     jnp.float32).at[:, :, :Wn].set(lse_bs)
    o, _ = _lse_merge(o_loc, lse_loc, o_b, lse_b)
    return o.astype(q.dtype)


def _ring_attention_flash(q, k, v, axis: str, causal: bool,
                          flash_opts: dict | None = None):
    """Flash-backed ring schedule: each hop runs the K/V-resident flash
    kernel on the local (Q shard, arriving K/V shard) pair and the
    results merge by lse weighting — the streaming-softmax fold lifted
    one level, from k-blocks within a shard to shards around the ring.

    Causality is blockwise by construction: an arriving shard is either
    fully in the past (unmasked flash), the diagonal shard (causal
    flash), or fully in the future (contributes nothing) — so the kernel
    itself only ever needs its LOCAL causal mask.
    """
    import jax as _jax

    from ..ops.flash import NEG_INF as _NI
    from ..ops.flash import flash_attention_lse

    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, D = q.shape
    perm = [(i, (i + 1) % P) for i in range(P)]
    on_tpu, mxu_dt = _flash_defaults(q)
    interpret = not on_tpu

    def hop_full(kv):
        kc, vc = kv
        return flash_attention_lse(q, kc, vc, causal=False,
                                   interpret=interpret, mxu_dtype=mxu_dt,
                                   **(flash_opts or {}))

    def hop_diag(kv):
        kc, vc = kv
        return flash_attention_lse(q, kc, vc, causal=True,
                                   interpret=interpret, mxu_dtype=mxu_dt,
                                   **(flash_opts or {}))

    def hop_dead(kv):
        # zeros derived from q AND the rotating k/v so this branch's
        # outputs carry the same device-variance (vma) as the flash
        # branches — lax.switch requires matching output types
        kc, vc = kv
        zkv = (jnp.sum(kc).astype(jnp.float32)
               + jnp.sum(vc).astype(jnp.float32)) * 0.0
        o_z = (q.astype(jnp.float32) * 0.0 + zkv).astype(q.dtype)
        lse_z = jnp.transpose(
            jnp.sum(o_z.astype(jnp.float32), axis=-1), (0, 2, 1)) + _NI
        return o_z, lse_z

    def step(s, carry):
        o, lse, kc, vc = carry
        src = (idx - s) % P
        if causal:
            branch = jnp.where(src == idx, 1,
                               jnp.where(src < idx, 0, 2))
            o_i, lse_i = lax.switch(branch, (hop_full, hop_diag, hop_dead),
                                    (kc, vc))
        else:
            o_i, lse_i = hop_full((kc, vc))
        # the running output carry stays fp32 for the whole ring (one
        # downcast after the loop), matching the dense path's contract
        o_new, lse_new = _lse_merge(o, lse, o_i, lse_i, _NI)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return o_new, lse_new, kc, vc

    # carry zeros derive from q/k/v so the device-variance types match
    # under any mesh composition (see the dense path's note)
    zkv = (jnp.sum(k).astype(jnp.float32)
           + jnp.sum(v).astype(jnp.float32)) * 0.0
    o0 = q.astype(jnp.float32) * 0.0 + zkv
    lse0 = jnp.transpose(jnp.sum(o0, axis=-1), (0, 2, 1)) + NEG_INF
    o, _lse, _, _ = lax.fori_loop(0, P, step, (o0, lse0, k, v))
    return o.astype(q.dtype)


def ulysses_attention(q, k, v, axis: str = "sp", causal: bool = False,
                      attn_fn=None, attn_fn_gqa_aware: bool = False):
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all reshards
    sequence↔heads so each member runs *full-sequence* attention on a
    head subset, then reshards back (built on the reference's alltoall,
    fw :2123-2218).  Requires H % P == 0.

    q: [B, T_local, H, D], k/v: [B, T_local, H or G, D] (grouped-query
    K/V reshard their own smaller head axis — G must also divide by P)
    → out: [B, T_local, H, D]

    A caller-supplied ``attn_fn`` receives EXPANDED K/V under GQA by
    default (safe for non-GQA-aware callables; correctness beats the
    bandwidth saving).  Pass ``attn_fn_gqa_aware=True`` when the
    callable handles a smaller K/V head axis itself (e.g. a partial of
    ops.flash.flash_attention) to keep the grouped layout and its
    HBM/memory saving.
    """
    P = lax.axis_size(axis)
    B, Tl, H, D = q.shape
    G = k.shape[2]
    if H % P != 0:
        raise ValueError(f"heads {H} not divisible by sp={P}")
    if G != H and (G % P != 0 or H % G != 0):
        raise ValueError(f"K/V heads {G} must divide q heads {H} and "
                         f"be divisible by sp={P} for Ulysses GQA")

    def seq_to_heads(x):
        # [B, Tl, h, D] -> [B, P*Tl, h/P, D] (h = that tensor's heads)
        h = x.shape[2]
        x = x.reshape(B, Tl, P, h // P, D)
        x = lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)
        return x.reshape(B, P * Tl, h // P, D)  # squeeze the split axis

    def heads_to_seq(x):
        x = x.reshape(B, P * Tl, 1, H // P, D)
        x = lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)
        return x.reshape(B, Tl, H, D)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # caller-supplied fns get expansion unless declared GQA-aware
    attn_fn_wants_expansion = (attn_fn is not None
                               and not attn_fn_gqa_aware)
    if attn_fn is None:
        if not pallas_interpret():
            # full-sequence local attention on the head subset runs the
            # flash kernel (same backend-resolved default as ring)
            from ..ops.flash import flash_attention

            mxu_dt = (q.dtype if q.dtype in (jnp.bfloat16, jnp.float16)
                      else jnp.float32)
            attn_fn = functools.partial(flash_attention, causal=causal,
                                        mxu_dtype=mxu_dt)
        else:
            attn_fn = functools.partial(_dense_attention, causal=causal)
            attn_fn_wants_expansion = True
    if kg.shape[2] != qg.shape[2] and attn_fn_wants_expansion:
        # a grouped head subset reaches a non-flash attention callable
        # (the dense default, or any caller-supplied fn — assumed NOT
        # GQA-aware; correctness beats the expansion saving there)
        kg, vg = expand_gqa_kv(kg, vg, qg.shape[2])
    og = attn_fn(qg, kg, vg)
    return heads_to_seq(og)


def expand_gqa_kv(k, v, n_q_heads: int):
    """Expand grouped K/V ([B, T, G, D]) to one head per q head by
    repeating each K/V head across its CONSECUTIVE group — the same
    row-sharing layout as the flash kernel's GQA index maps (q head n
    reads K/V head n // (H/G)).  The one place the expansion layout is
    defined; dense reference paths call this instead of repeating
    inline."""
    group = n_q_heads // k.shape[2]
    if group == 1:
        return k, v
    return (jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2))


def _dense_attention(q, k, v, causal: bool = False,
                     window: int | None = None):
    """Reference dense attention [B, T, H, D] (fp32 accumulation).
    `window` (causal only) restricts each row to its trailing `window`
    columns — the banded reference the flash grid schedules match."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        T = q.shape[1]
        qpos = lax.broadcasted_iota(jnp.int32, (T, T), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (T, T), 1)
        keep = qpos >= kpos
        if window is not None:
            keep = keep & (qpos - kpos < window)
        s = jnp.where(keep[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
