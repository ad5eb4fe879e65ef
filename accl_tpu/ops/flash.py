"""Flash attention as a Pallas TPU kernel.

The MXU-resident attention block for the model families: tiled
QK^T -> online-softmax -> PV with the running (max, denominator)
carried across K blocks, so the [Tq, Tk] score matrix never
materializes in HBM.  Both schedules also emit log-sum-exp statistics,
which is what lets distributed callers fold partial attentions.

This is the local-compute half of the long-context story: ring
attention (accl_tpu.parallel.ring_attention) rotates K/V shards around
the ICI ring — the reference's fused recv-reduce-send ring schedule
(ccl_offload_control.c:1404-1502, :718) — and each arriving block is
consumed by exactly this kernel's math, with the shard-level merge
using the lse outputs.

Two schedules share one online-softmax fold and one wrapper:
- resident: the whole K/V row pinned in VMEM per batch-head (fetched
  once; fastest while it fits),
- grid: K/V streamed per (q-block, k-block) grid cell (any T).
The wrapper auto-switches on K/V size; `kernel=` forces either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_LOG2E = 1.4426950408889634  # log2(e)
_LN2 = 0.6931471805599453    # ln(2)


def _softmax_fold(q, kb, vb, acc, m_prev, l_prev, *, mask, mxu_dtype,
                  static_max=None):
    """One online-softmax block fold shared by BOTH kernel schedules —
    the numerically delicate part (shift clamp so fully-masked rows
    don't produce exp(+big), masked-p zeroing, alpha rescale of the
    running state) lives exactly once.

    The fold runs in the LOG2 domain: q arrives PRE-SCALED by
    log2(e)/sqrt(D) (one [bq, D] multiply replaces a [bq, bk] VPU pass
    per fold — the kernel is VPU-bound at D=64, so score-matrix passes
    are the budget), so scores are log2-scaled logits, probabilities are
    exp2(s - m), and the TRUE log-sum-exp is m*ln2 + ln(l) — `_finalize`
    converts.  `p` values are identical to the natural-base fold
    (exp2(log2e*(x - m_nat)) == exp(x - m_nat)), so acc/l match exactly.

    kb/vb: [bk, D] (mxu dtype); acc/m/l are f32 running state.  `mask`
    is None or (row0, col0, window) block offsets for the causal
    row >= col test, with `window` further restricting each row to its
    trailing `window` columns (None = unwindowed).
    Returns (acc', m', l').

    FUSED-DENOMINATOR mode (`l_prev is None`): vb carries an appended
    ones column and acc the matching accumulator column, so the row-sum
    of p rides the PV matmul on the MXU and the explicit `jnp.sum` VPU
    pass disappears — free where D pads to the same lane tile anyway
    (D=64 -> 65 both pad to 128).  Returns (acc', m', None).

    STATIC-MAX mode (`static_max` a float): probabilities are
    exp2(s - static_max) with NO running max — the max reduction, the
    shift clamp, the alpha rescale of acc/l and the masked-p re-zero
    all disappear from the VPU budget (the fold is VPU-bound at
    D=128: these passes are the measured ceiling).  Exact as long as
    scaled logits stay within f32 range of the pin (|s - static_max|
    < ~126; see flash_attention_packed docs).  m carries through
    untouched; _finalize receives m = static_max (dead rows
    NEG_INF)."""
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return _fold_consume(s, vb, acc, m_prev, l_prev, mask=mask,
                         mxu_dtype=mxu_dtype, static_max=static_max)


def _fold_consume(s, vb, acc, m_prev, l_prev, *, mask, mxu_dtype,
                  static_max=None):
    """The softmax/PV half of the fold, consuming a PRECOMPUTED score
    block `s` [bq, bk] (raw, unmasked).  Split out so the skewed
    schedule can issue block j+1's QK^T before consuming block j's
    scores — numerics identical to :func:`_softmax_fold`, which now
    delegates here."""
    block_q, block_k = s.shape
    masked = mask is not None
    if masked:
        row0, col0, window = mask
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = col0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        keep = rows >= cols
        if window is not None:
            # sliding window: row r attends cols (r-window, r]
            keep = keep & (rows - cols < window)
        s = jnp.where(keep, s, NEG_INF)
    if static_max is not None:
        # static pin (see _softmax_fold): exp2(NEG_INF - pin) flushes
        # to +0.0 in f32 — masked cells need no re-zero, dead rows
        # produce l = 0 (the _finalize guard)
        p = jnp.exp2(s - static_max)
        l_new = (None if l_prev is None
                 else l_prev + jnp.sum(p, axis=-1, keepdims=True))
        acc_new = acc + jax.lax.dot_general(
            p.astype(mxu_dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_prev, l_new
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    # fully-masked block rows keep m at NEG_INF; exp2(s - NEG_INF) would
    # be exp2(+big) — guard by clamping the shift
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp2(s - shift)                         # [bq, bk]
    if masked:
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp2(m_prev - shift))     # rescale of old state
    l_new = (None if l_prev is None
             else alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True))
    acc_new = acc * alpha + jax.lax.dot_general(
        p.astype(mxu_dtype), vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _finalize(acc, m, lsum, o_ref, lse_ref, row_off=None):
    """Write the normalized output and the lse statistics (shared by
    both schedules so the denom/dead-row guards stay identical).  `m` is
    a log2-domain running max (see _softmax_fold); the emitted lse is in
    NATURAL log units — the cross-shard merge contract.

    `row_off` selects a row range of the block to write (the q-tile
    interleaved schedule finalizes per sub-tile); offset stores are used
    rather than `.at[]` ref views because a view of the lse block slices
    its tile-padded minor dim, which Mosaic rejects."""
    from jax.experimental import pallas as pl

    denom = jnp.where(lsum == 0.0, 1.0, lsum)
    out = (acc / denom).astype(o_ref.dtype)
    dead = m <= NEG_INF / 2
    lse = jnp.where(dead, NEG_INF,
                    m * _LN2 + jnp.log(jnp.maximum(lsum, 1e-38)))
    # lse block is [bq, 1] — the trailing unit dim keeps it tile-aligned
    # for Mosaic (second-minor bq % 8 == 0, minor == full)
    if row_off is None:
        o_ref[0] = out
        lse_ref[0] = lse
    else:
        rows = acc.shape[0]
        o_ref[0, pl.ds(row_off, rows), :] = out
        lse_ref[0, pl.ds(row_off, rows), :] = lse


def _causal_block_bounds(iq, block_q, block_k, nk_total):
    """(n_past, n_live) k-block bounds for q-block `iq` under the
    causal mask: blocks [0, n_past) are strictly past (no mask work),
    [n_past, n_live) straddle the diagonal (masked), [n_live, nk) are
    strictly future (skipped).  Shared by every resident-style
    schedule so the bounds cannot desynchronize between kernels."""
    n_past = (iq * block_q) // block_k
    n_live = (iq * block_q + block_q + block_k - 1) // block_k
    return n_past, jnp.minimum(n_live, nk_total)


def _run_block_loops(body, carry, causal, iq, block_q, block_k,
                     nk_total):
    """Drive `body(j, carry, masked)` over the k-blocks: the causal
    split (unmasked past bulk, masked diagonal epilogue) or the full
    unmasked range.  One copy of the loop scaffolding for every
    resident-style schedule — the carry (including the skew schedule's
    prefetched score block) crosses the loop boundary intact."""
    from jax import lax as jlax

    if causal:
        n_past, n_live = _causal_block_bounds(iq, block_q, block_k,
                                              nk_total)
        carry = jlax.fori_loop(0, n_past,
                               lambda j, c: body(j, c, masked=False),
                               carry)
        return jlax.fori_loop(n_past, n_live,
                              lambda j, c: body(j, c, masked=True),
                              carry)
    return jlax.fori_loop(0, nk_total,
                          lambda j, c: body(j, c, masked=False), carry)


def _flash_kernel_grid(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
                       *, scale: float, causal: bool, block_q: int,
                       block_k: int, chunk_k: int, nk: int,
                       nk_total: int | None = None, mxu_dtype,
                       kv_resident: bool = False, q_tiles: int = 1,
                       window=None, static_max=None):
    """Streaming schedule: grid (bh, q_block, k_block); K/V blocks
    arrive per grid cell; the accumulator lives in VMEM scratch across
    the sequential k steps of one (bh, q_block) cell.  Each arriving
    block is folded as an unrolled run of chunk_k sub-folds so the MXU
    stays busy while the VPU runs the previous chunk's softmax (same
    pipelining rationale as the resident kernel).  q_tiles > 1 splits
    the q block into independent sub-tile chains whose folds interleave
    (see the resident kernel's docstring) — the long-context schedule's
    version of the same MXU/VPU overlap."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    j = pl.program_id(2)
    # with a sliding window the k grid dimension is BOUNDED: it spans
    # only the blocks a q block can see (O(window) of them), and the
    # K/V index maps fetch from the same shifted base — out-of-window
    # blocks are never DMA'd, not merely predicated off.  ik is the
    # REAL k-block index the liveness/mask math needs.
    ik = j + (_window_first_block(iq, block_q, block_k, window)
              if window is not None else 0)
    tq = block_q // q_tiles

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # a causal k-block strictly in this q-block's future contributes
    # nothing — skip its whole body (roughly halves the MXU work).
    # Blocks strictly in the past need no mask at all; only the blocks
    # straddling the diagonal (or the window edge) pay the iota/where
    # lane work.  ONE liveness helper is shared with both backward
    # kernels so the schedules cannot desynchronize.
    live, diag = _grid_live_masked(iq, ik, block_q, block_k, causal,
                                   window)
    if window is not None and nk_total is not None:
        # phantom tail cells of the bounded span (clamped fetches past
        # the real k range) stay dead regardless of the mask algebra —
        # here causality already kills them, but the guard keeps the
        # invariant explicit and future-proof
        live = live & (ik < nk_total)
        diag = diag & live

    q = (q_ref[0] * scale).astype(mxu_dtype)  # pre-scale once per block
    qs = [q[t * tq:(t + 1) * tq] for t in range(q_tiles)]

    def body(masked: bool):
        carries = [(acc[pl.ds(t * tq, tq), :], m_s[pl.ds(t * tq, tq), :],
                    l_s[pl.ds(t * tq, tq), :]) for t in range(q_tiles)]
        for c in range(block_k // chunk_k):
            off = ik * block_k + c * chunk_k
            # kv_resident: the refs hold the WHOLE row (the index map is
            # pinned, so Pallas fetched it once per batch-head) and the
            # block offset is applied here instead of by the pipeline
            base = off if kv_resident else c * chunk_k
            kb = k_ref[0, pl.ds(base, chunk_k), :].astype(mxu_dtype)
            vb = v_ref[0, pl.ds(base, chunk_k), :].astype(mxu_dtype)
            carries = [
                _softmax_fold(qs[t], kb, vb, *carries[t],
                              mask=((iq * block_q + t * tq, off, window)
                                    if masked else None),
                              mxu_dtype=mxu_dtype,
                              static_max=static_max)
                for t in range(q_tiles)]
        for t, (a, m, lsum) in enumerate(carries):
            acc[pl.ds(t * tq, tq), :] = a
            m_s[pl.ds(t * tq, tq), :] = m
            l_s[pl.ds(t * tq, tq), :] = lsum

    if causal:
        @pl.when(diag)
        def _diag_body():
            body(masked=True)

        @pl.when(live & jnp.logical_not(diag))
        def _past_body():
            body(masked=False)
    else:
        body(masked=False)

    @pl.when(j == nk - 1)
    def _fin():
        if static_max is None:
            m_fin = m_s[:]
        else:
            # the m scratch was never updated (see _softmax_fold's
            # static mode): reconstruct the pin for live rows and
            # NEG_INF for fully-dead ones so _finalize's lse/dead-row
            # algebra stays shared
            m_fin = jnp.where(l_s[:] == 0.0, NEG_INF, static_max)
        _finalize(acc[:], m_fin, l_s[:], o_ref, lse_ref)


def _flash_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                           scale: float, causal: bool, block_q: int,
                           block_k: int, chunk_k: int, T: int, mxu_dtype,
                           q_tiles: int = 1, fuse_denom: bool = False,
                           static_max=None):
    """K/V-resident schedule: the whole K/V row for this batch-head sits
    in VMEM (fetched ONCE — the grid variant refetches it per q-block,
    which is the streaming bound at small-to-medium T).

    Three throughput tricks beyond the plain fold:
    - when the input dtype differs from the MXU dtype, K/V are cast ONCE
      per batch-head into VMEM scratch at the first q-block (the naive
      per-fold cast re-converts the same rows nq times — measured as a
      double-digit share of kernel time at D=128);
    - each block_k fold is an UNROLLED run of chunk_k sub-folds, so
      Mosaic can issue chunk c+1's independent QK^T matmul while the VPU
      works on chunk c's softmax — without this the MXU idles during
      every max/exp2/sum pass and the kernel tops out near 50% MXU;
    - q_tiles > 1 splits the q block into INDEPENDENT sub-tiles whose
      folds are interleaved in program order: tile A's softmax (VPU) has
      no data dependence on tile B's matmuls (MXU), so the scheduler can
      run them concurrently — at D=128 one softmax pass costs about as
      much VPU time as the fold's two matmuls cost MXU time, so a single
      dependence chain caps the kernel near 50% MXU no matter how well
      a lone chain pipelines."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    D = q_ref.shape[-1]
    nk_total = T // block_k
    n_chunks = block_k // chunk_k
    tq = block_q // q_tiles
    # pre-scaled independent q sub-tiles (see q_tiles note above)
    qs = [(q_ref[0, pl.ds(t * tq, tq), :] * scale).astype(mxu_dtype)
          for t in range(q_tiles)]

    if fuse_denom:
        # fused-denominator layout (see _softmax_fold): the
        # ones-extended V lives in scratch, built once per batch-head;
        # K joins it only when it needs a dtype cast — otherwise it is
        # read straight from its ref (review finding: an unconditional
        # K copy wasted a (T, D) VMEM buffer when dtypes already match)
        *k_scr, vb_s = scratch
        @pl.when(iq == 0)
        def _build_kv():
            if k_scr:
                k_scr[0][:] = k_ref[0].astype(mxu_dtype)
            vb_s[:] = jnp.concatenate(
                [v_ref[0].astype(mxu_dtype),
                 jnp.ones((T, 1), mxu_dtype)], axis=1)

        def kv_chunk(off):
            kb = (k_scr[0][pl.ds(off, chunk_k), :] if k_scr
                  else k_ref[0, pl.ds(off, chunk_k), :].astype(mxu_dtype))
            return kb, vb_s[pl.ds(off, chunk_k), :]
    elif scratch:
        kb_s, vb_s = scratch
        # grid order within one batch-head is sequential (the iq
        # dimension is marked "arbitrary"), so the cast done at the
        # first q-block is visible to the rest
        @pl.when(iq == 0)
        def _cast_kv():
            kb_s[:] = k_ref[0].astype(mxu_dtype)
            vb_s[:] = v_ref[0].astype(mxu_dtype)

        def kv_chunk(off):
            return (kb_s[pl.ds(off, chunk_k), :],
                    vb_s[pl.ds(off, chunk_k), :])
    else:
        # no scratch: cast PER CHUNK like the grid schedule, so
        # mxu_dtype always governs the matmul input format (a no-op
        # when the input already arrives in MXU dtype)
        def kv_chunk(off):
            return (k_ref[0, pl.ds(off, chunk_k), :].astype(mxu_dtype),
                    v_ref[0, pl.ds(off, chunk_k), :].astype(mxu_dtype))

    def step(j, carries, masked):
        # unrolled chunk run — `for c in range(...)` is static, letting
        # the compiler software-pipeline MXU against VPU across chunks
        # and across the independent q sub-tiles within one chunk
        for c in range(n_chunks):
            off = j * block_k + c * chunk_k
            kb, vb = kv_chunk(off)
            nxt = []
            for t in range(q_tiles):
                acc, m_prev, l_prev = carries[t]
                mask = ((iq * block_q + t * tq, off, None)
                        if masked else None)
                nxt.append(_softmax_fold(qs[t], kb, vb, acc, m_prev,
                                         l_prev, mask=mask,
                                         mxu_dtype=mxu_dtype,
                                         static_max=static_max))
            carries = tuple(nxt)
        return carries

    acc_d = D + 1 if fuse_denom else D
    carry = tuple((jnp.zeros((tq, acc_d), jnp.float32),
                   jnp.full((tq, 1), NEG_INF, jnp.float32),
                   None if fuse_denom else jnp.zeros((tq, 1), jnp.float32))
                  for _ in range(q_tiles))
    carry = _run_block_loops(step, carry, causal, iq, block_q,
                             block_k, nk_total)
    for t in range(q_tiles):
        acc, m, lsum = carry[t]
        if fuse_denom:
            acc, lsum = acc[:, :D], acc[:, D:]
        if static_max is not None:
            # the carry's m was never updated — reconstruct the value
            # _finalize's lse/dead-row algebra expects: the pin for
            # live rows, NEG_INF for fully-dead rows (l stayed 0)
            m = jnp.where(lsum == 0.0, NEG_INF, static_max)
        _finalize(acc, m, lsum, o_ref, lse_ref,
                  row_off=None if q_tiles == 1 else t * tq)


def _flash_kernel_resident_skew(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                                scale: float, causal: bool, block_q: int,
                                block_k: int, T: int, mxu_dtype):
    """Software-pipelined resident schedule (single fold chain): the
    QK^T for k-block j+1 is issued BEFORE block j's softmax/PV consume
    its scores, carrying the prefetched score block [bq, bk] through
    the fori_loop.  In the plain chain the next matmul depends on the
    fold's full VPU pass (via the alpha rescale), so the MXU idles
    through every max/exp2/sum; the skew makes the lookahead matmul
    data-independent of the current consume, exposing a legal MXU/VPU
    overlap window to the static scheduler instead of hoping it finds
    one inside a serialized body.  The lookahead at the last block
    clamps its read and is discarded.  Numerics are bit-identical to
    the plain resident chain (same _fold_consume, same fold order).

    MEASURED RESULT (honest-timing r04 sweeps): consistently SLOWER
    than the plain chain (0.21-0.22 vs 0.34-0.36 MXU fraction at
    D=128) — the [bq, bk] f32 score block carried through the
    fori_loop costs more VMEM traffic than the exposed overlap buys.
    Kept as a selectable schedule so the negative result stays
    reproducible (`kernel="resident_skew"`); not in the auto table."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    nk_total = T // block_k
    q = (q_ref[0] * scale).astype(mxu_dtype)

    def score(j):
        # clamp the lookahead read: at the final block this computes a
        # discarded extra score block against the last K rows
        off = jnp.minimum(j, nk_total - 1) * block_k
        kb = k_ref[0, pl.ds(off, block_k), :].astype(mxu_dtype)
        return jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def body(j, carry, masked):
        acc, m, lsum, s_cur = carry
        # lookahead FIRST in program order — independent of the consume
        s_nxt = score(j + 1)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(mxu_dtype)
        mask = (iq * block_q, j * block_k, None) if masked else None
        acc, m, lsum = _fold_consume(s_cur, vb, acc, m, lsum, mask=mask,
                                  mxu_dtype=mxu_dtype)
        return acc, m, lsum, s_nxt

    D = q_ref.shape[-1]
    carry = (jnp.zeros((block_q, D), jnp.float32),
             jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             score(0))
    carry = _run_block_loops(body, carry, causal, iq, block_q,
                             block_k, nk_total)
    acc, m, lsum, _ = carry
    _finalize(acc, m, lsum, o_ref, lse_ref)


def _vma_of(*xs):
    """Join of the inputs' device-variance sets when tracing inside
    shard_map (None outside); pallas_call out_shapes must carry it."""
    vma = None
    for x in xs:
        v = getattr(getattr(x, "aval", None), "vma", None)
        if v:
            vma = v if vma is None else (vma | v)
    return vma


def _sds(shape, dtype, vma):
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


#: K/V rows larger than this stay on the streaming (grid) kernel; below
#: it both rows fit VMEM comfortably alongside the double-buffered q/o
#: blocks (~16 MB/core)
_RESIDENT_KV_BYTES = 6 << 20

#: Auto-schedule defaults applied when the caller leaves q_tiles=None
#: (the public default).  Tuned against the live-chip schedule sweep
#: (scripts/flash_tune.py / scripts/chip_session.py over
#: accl_tpu/bench/flash_sweep.py) under the min-RTT timing harness
#: (bench/timing.py — earlier sweeps banked an inflated sync estimate
#: and their numbers were unusable): across four honest windows at
#: D=128 the plain single chain and the two-chain q-tile interleave
#: are statistically tied (0.29-0.39 MXU fraction, ordering flips
#: window to window) while split folds (chunk_k < block_k) and qt4
#: consistently lose — so the auto table keeps the SIMPLEST schedule.
#: Explicit q_tiles/chunk_k always win over the auto table.
_AUTO_Q_TILES = 1
_AUTO_CHUNK_K = None  # None = fold whole K blocks (no sub-chunk split)


def _snap_chunk(req: int, blk: int) -> int:
    """Largest divisor of `blk` at or below `req`, never under the
    8-row tile floor (falls back to the whole block) — the one snapping
    rule for every sub-chunk unroll (forward folds and backward cells).
    """
    return next((d for d in range(min(req, blk), 7, -1)
                 if blk % d == 0), blk)


def _resolve_schedule(T, Tk, D, qdtype, causal, block_q, block_k,
                      interpret, mxu_dtype, kernel, chunk_k,
                      kv_cast_scratch, q_tiles, fuse_denom,
                      window=None, static_max=None):
    """Static schedule resolution shared by the head-packed and BTHD
    entries: block shrinking, chunk snapping, kernel/auto selection and
    the tuned-auto q_tiles/fuse_denom choices.  Returns the cfg tuple
    consumed by the forward/backward impls."""
    # shrink blocks (by halving, down to the 8-row f32 tile floor) until
    # they divide their sequence length, so defaults keep working for
    # any T smaller defaults accepted
    bq, bk = min(block_q, T), min(block_k, Tk)
    while T % bq != 0 and bq > 8:
        bq //= 2
    while Tk % bk != 0 and bk > 8:
        bk //= 2
    if T % bq != 0 or Tk % bk != 0:
        raise ValueError(
            f"sequence lengths {T}/{Tk} not divisible by blocks ({bq}, {bk})")
    # sub-fold chunk (None = whole block): smaller chunks give the
    # compiler MXU/VPU pipelining slack at the price of smaller matmuls.
    # Snap to the largest divisor of bk at or below the request, never
    # under the 8-row tile floor (halving alone can decay 12->3->1)
    ck = bk if chunk_k is None else _snap_chunk(chunk_k, bk)

    mxu_dtype = jnp.dtype(mxu_dtype)
    # one-shot K/V cast scratch is OPT-IN: it trades the per-fold cast
    # for a serialized q-block order ("arbitrary" semantics), a tradeoff
    # that must be measured per chip generation
    needs_cast = kv_cast_scratch and qdtype != mxu_dtype

    # q_tiles=None (the public default) opts into the auto schedule:
    # tuned (q_tiles, chunk_k) applied after the kernel resolves below.
    # Explicit q_tiles (incl. 1 = plain single-chain) is always honored.
    auto_sched = q_tiles is None
    if auto_sched:
        q_tiles = _AUTO_Q_TILES
    elif q_tiles < 1:
        raise ValueError(f"q_tiles={q_tiles} must be >= 1")
    # fuse_denom=None (the public default) is the auto choice, resolved
    # after the kernel lands below; explicit True/False always wins
    auto_fd = fuse_denom is None
    if not auto_fd and fuse_denom and kernel not in ("resident", "auto"):
        # an EXPLICIT non-resident kernel with the resident-only option
        # is a contradiction — silently not applying it would be a perf
        # lie.  (Under "auto" it is a tuning HINT and drops gracefully
        # below when the schedule lands on grid.  q_tiles is supported
        # by every schedule.)
        raise ValueError(
            f"fuse_denom is a resident-schedule option (kernel={kernel!r})")

    kv_bytes = 2 * Tk * D * (qdtype.itemsize
                             + (mxu_dtype.itemsize if needs_cast else 0))
    # fuse_denom's ones-extended V (and K-cast, when dtypes differ)
    # scratch counts against the same VMEM residency budget
    fd_scr_bytes = (
        Tk * (D + 1 + (D if qdtype != mxu_dtype else 0))
        * mxu_dtype.itemsize)
    auto_kernel = kernel == "auto"
    if auto_kernel:
        kernel = ("resident" if kv_bytes <= _RESIDENT_KV_BYTES
                  else "grid")
    if kernel not in ("resident", "grid", "grid_resident",
                      "resident_skew"):
        raise ValueError(f"unknown flash kernel {kernel!r}")
    if kernel == "resident_skew":
        # same rule as the fuse_denom check above: silently ignoring an
        # explicit schedule option would record fake sweep results
        if q_tiles > 1:
            raise ValueError("resident_skew is a single-chain schedule "
                             "(the skewed score carry IS its overlap "
                             "mechanism); q_tiles > 1 is not supported")
        if chunk_k is not None:
            raise ValueError("resident_skew folds whole K blocks (the "
                             "score carry spans block_k); chunk_k is "
                             "not supported")
        if kv_cast_scratch:
            raise ValueError("resident_skew casts K/V per block read; "
                             "kv_cast_scratch is not supported")
    if auto_fd:
        # the ones column rides free only when D and D+1 pad to the
        # same 128-lane tile (D=64 -> 65 both pad to 128; D=128 -> 129
        # pads to 256, doubling every PV matmul) — measured at D=64 as
        # the fastest schedule (0.19 vs 0.16 MXU frac, honest-timing
        # r04 sweep; confirmed in every window swept)
        fuse_denom = (kernel == "resident" and D % 128 != 0
                      and kv_bytes + fd_scr_bytes <= _RESIDENT_KV_BYTES)
    elif fuse_denom and auto_kernel:
        # distributed callers forward tuned opts without knowing each
        # shard's size (docs/parallelism.md) — under kernel="auto" the
        # resident-only hint drops when the schedule lands on grid (or
        # when its scratch would blow the residency budget); q_tiles
        # carries over to the grid schedule.  An EXPLICIT resident
        # kernel keeps the explicit option unconditionally.
        if kernel != "resident" \
                or kv_bytes + fd_scr_bytes > _RESIDENT_KV_BYTES:
            fuse_denom = False

    if auto_sched and chunk_k is None and _AUTO_CHUNK_K is not None:
        ck = _snap_chunk(_AUTO_CHUNK_K, bk)

    # snap q_tiles down until the sub-tiles are 8-row-aligned divisors
    # of the (possibly auto-shrunk) q block — the same keep-working
    # contract as the block halving and chunk snapping above
    while q_tiles > 1 and (bq % q_tiles != 0
                           or (bq // q_tiles) % 8 != 0):
        q_tiles -= 1

    if window is not None:
        # sliding-window attention: the streaming (grid) schedules own
        # the block liveness logic; the resident family's fori bounds
        # do not model a window
        if not causal:
            raise ValueError("window requires causal=True (a sliding "
                             "window is a trailing-context mask)")
        if window < 1:
            raise ValueError(f"window={window} must be >= 1")
        if kernel == "resident" and auto_kernel:
            kernel = "grid"   # auto landed on resident: move to grid
        if kernel not in ("grid", "grid_resident"):
            # same explicit-option contract as fuse_denom/resident_skew
            # above: silently running a different schedule than the one
            # named would record fake sweep results
            raise ValueError("window is a grid-schedule option "
                             f"(kernel={kernel!r})")
        fuse_denom = False    # resident-only option can't apply
    if static_max is not None:
        if kernel == "resident_skew":
            # the skew schedule's carried score block assumes the
            # dynamic fold; silently running it would record fake
            # sweep results (same contract as its other options)
            raise ValueError("static_max is not supported by the "
                             "resident_skew schedule")
        static_max = float(static_max)
    return (causal, bq, bk, ck, interpret, mxu_dtype, kernel,
            needs_cast, q_tiles, fuse_denom, window, static_max)


def _flash_call_packed(qp, kp, vp, causal, block_q, block_k, interpret,
                       mxu_dtype, kernel, chunk_k=None,
                       kv_cast_scratch=False, q_tiles=None,
                       fuse_denom=None, window=None, static_max=None):
    """Core entry on HEAD-PACKED operands [N, T, D] (N = batch x heads
    flattened — the splash-attention layout).  This is the zero-copy
    path: no transposes touch HBM; callers that keep activations packed
    (the model families do) pay only the kernel itself.
    GROUPED-QUERY ATTENTION (GQA): when K/V arrive with FEWER packed
    heads than q — shape [Nk, Tk, D] with N % Nk == 0 — each K/V head
    serves N/Nk consecutive q heads (q row n reads K/V row
    n // (N // Nk)).  Zero-copy on BOTH paths: the forward's K/V block
    index maps share rows across the group, and the backward reads the
    grouped K/V the same way (dq kernel, b//G maps) while the dkv
    kernel folds the whole group's dK/dV on an extended accumulation
    axis — no expansion touches HBM in either direction.

    Returns (out [N, T, D], lse [N, T] f32)."""
    N, T, D = qp.shape
    Tk = kp.shape[1]
    if (kp.shape != vp.shape or kp.shape[2] != D
            or kp.shape[0] == 0 or N % kp.shape[0] != 0):
        raise ValueError(f"k/v shape {kp.shape}/{vp.shape} incompatible "
                         f"with q {qp.shape} (K/V heads must divide "
                         f"q heads for GQA)")
    if causal and Tk != T:
        raise ValueError("causal masking requires Tq == Tk "
                         "(cross-length attention has no diagonal)")
    kv_group = N // kp.shape[0]
    # everything static is resolved; the traced part goes through the
    # custom-vjp boundary so jax.grad works on every entry point
    cfg = _resolve_schedule(T, Tk, D, qp.dtype, causal, block_q,
                            block_k, interpret, mxu_dtype, kernel,
                            chunk_k, kv_cast_scratch, q_tiles,
                            fuse_denom, window, static_max) + (kv_group,)
    return _flash_packed_diff(qp, kp, vp, cfg)


def _flash_forward_impl(qp, kp, vp, cfg):
    """The schedule dispatch — resolved static config only (see
    `_flash_call_packed`, which owns validation/auto-tuning)."""
    from jax.experimental import pallas as pl

    (causal, bq, bk, ck, interpret, mxu_dtype, kernel, needs_cast,
     q_tiles, fuse_denom, window, static_max, kv_group) = cfg
    g = kv_group  # q-heads per K/V head (1 = plain MHA)
    N, T, D = qp.shape
    Tk = kp.shape[1]
    nq, nk = T // bq, Tk // bk
    scale = _LOG2E / float(D) ** 0.5
    vma = _vma_of(qp, kp, vp)

    q_spec3 = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                           memory_space=pltpu.VMEM)
    out_shapes = (_sds((N, T, D), qp.dtype, vma),
                  _sds((N, T, 1), jnp.float32, vma))

    if kernel in ("resident", "resident_skew"):
        grid = (N, nq)
        q_spec = pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0),
                              memory_space=pltpu.VMEM)
        kv_spec = pl.BlockSpec((1, Tk, D), lambda b, i: (b // g, 0, 0),
                               memory_space=pltpu.VMEM)
        lse_spec = pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0),
                                memory_space=pltpu.VMEM)
        if kernel == "resident_skew":
            # single-chain, per-block-read casts: no scratch variants
            scratch = []
            kfn = functools.partial(
                _flash_kernel_resident_skew, scale=scale, causal=causal,
                block_q=bq, block_k=bk, T=Tk, mxu_dtype=mxu_dtype)
        else:
            # one-time K/V cast scratch (see kernel docstring) — only
            # when the input is not already in MXU format.  fuse_denom
            # builds the ones-extended V in scratch regardless of dtype.
            if fuse_denom:
                scratch = ([pltpu.VMEM((Tk, D), mxu_dtype)]
                           if qp.dtype != mxu_dtype else [])
                scratch += [pltpu.VMEM((Tk, D + 1), mxu_dtype)]
            elif needs_cast:
                scratch = [pltpu.VMEM((Tk, D), mxu_dtype),
                           pltpu.VMEM((Tk, D), mxu_dtype)]
            else:
                scratch = []
            kfn = functools.partial(
                _flash_kernel_resident, scale=scale, causal=causal,
                block_q=bq, block_k=bk, chunk_k=ck, T=Tk,
                mxu_dtype=mxu_dtype, q_tiles=q_tiles,
                fuse_denom=fuse_denom, static_max=static_max)
        out, lse = pl.pallas_call(
            kfn, out_shape=out_shapes, grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=(q_spec, lse_spec),
            scratch_shapes=scratch,
            # with cast/fused scratch the q-blocks of one batch-head must
            # run in-order ("arbitrary") so the iq==0 build is visible to
            # the rest; without it every cell is independent ("parallel")
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    ("parallel", "arbitrary")
                    if (needs_cast or fuse_denom)
                    else ("parallel", "parallel"))),
            interpret=interpret,
        )(qp, kp, vp)
    else:
        # with a sliding window the k grid dimension is BOUNDED to the
        # O(window/bk) blocks a q block can actually see; the K/V index
        # maps fetch from the same shifted base (clamped at the last
        # block — a clamped fetch belongs to a dead cell), so
        # out-of-window K/V blocks are never DMA'd
        if window is not None:
            nk_eff = min(nk, (window - 1 + bq + bk - 1) // bk + 1)

            def _kv_block(i, j):
                first = _window_first_block(i, bq, bk, window)
                return jnp.minimum(first + j, nk - 1)
        else:
            nk_eff = nk

            def _kv_block(i, j):
                return j
        grid = (N, nq, nk_eff)
        kv_resident = kernel == "grid_resident"
        if kv_resident:
            # whole-row K/V block with a PINNED index map: Pallas only
            # re-DMAs a block whose index changes, so the row is fetched
            # once per batch-head while the cells keep the grid
            # schedule's static predication and scratch carries
            kv_spec = pl.BlockSpec((1, Tk, D),
                                   lambda b, i, j: (b // g, 0, 0),
                                   memory_space=pltpu.VMEM)
        else:
            kv_spec = pl.BlockSpec(
                (1, bk, D),
                lambda b, i, j: (b // g, _kv_block(i, j), 0),
                memory_space=pltpu.VMEM)
        lse_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                                memory_space=pltpu.VMEM)
        kfn = functools.partial(
            _flash_kernel_grid, scale=scale, causal=causal, block_q=bq,
            block_k=bk, chunk_k=ck, nk=nk_eff, nk_total=nk,
            mxu_dtype=mxu_dtype,
            kv_resident=kv_resident, q_tiles=q_tiles, window=window,
            static_max=static_max)
        out, lse = pl.pallas_call(
            kfn, out_shape=out_shapes, grid=grid,
            in_specs=[q_spec3, kv_spec, kv_spec],
            out_specs=(q_spec3, lse_spec),
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),   # acc
                pltpu.VMEM((bq, 1), jnp.float32),   # running max
                pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            ],
            # the k dimension carries the accumulator (sequential); the
            # bh/q-block dims are independent
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(qp, kp, vp)

    return out, lse.reshape(N, T)


# ---------------------------------------------------------------------------
# backward pass (jax.custom_vjp)
# ---------------------------------------------------------------------------
#
# The standard flash-attention backward, TPU-shaped: with the saved
# (out, lse), normalized probabilities rebuild per block as
# P = exp2(s2 - lse2) (log2 domain like the forward), and
#
#   dV_j  = sum_i P_ij dO_i
#   dS_ij = P_ij * (dO_i . V_j - dvec_i),  dvec_i = dO_i . out_i - dlse_i
#   dQ_i  = a * sum_j dS_ij K_j,   dK_j = a * sum_i dS_ij Q_i
#
# (the dlse term folds the lse output's cotangent in — ring attention
# differentiates through its lse-weighted shard merge).  Two grid
# kernels: dQ accumulates over k blocks per q block; dK/dV accumulate
# over q blocks per k block.  Causal cells are predicated off exactly
# like the forward grid schedule.

def _flash_bwd_p_block(q2, kb, l2, row0, col0, masked, window=None):
    """Rebuild the normalized probability block [rows(q2), rows(kb)]
    from prescaled q2 (a*log2e folded in) and the log2-domain lse; dead
    rows (lse = NEG_INF, fully-masked forward) produce zeros.  `masked`
    applies the causal row >= col test (AND the sliding-window
    row - col < window test when set) against the (row0, col0) global
    offsets — callers predicate it to the straddling cells only (past
    cells need no mask; same lane-work split as the forward grid
    kernel)."""
    rq, rk = q2.shape[0], kb.shape[0]
    s2 = jax.lax.dot_general(q2, kb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    p = jnp.where(l2 <= NEG_INF / 2, 0.0, jnp.exp2(s2 - l2))
    if masked:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (rq, rk), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (rq, rk), 1)
        keep = rows >= cols
        if window is not None:
            keep = keep & (rows - cols < window)
        p = jnp.where(keep, p, 0.0)
    return p


def _window_first_block(iq, block_q, block_k, window):
    """Index of the first k block any row of q-block `iq` can see under
    the sliding window — the k-grid base the bounded schedule and its
    K/V index maps share."""
    lo = iq * block_q - (window - 1)     # earliest visible column
    return jnp.maximum(lo, 0) // block_k


def _grid_live_masked(iq, ik, bq, bk, causal, window=None):
    """(live, masked) cell predicates shared by the forward grid kernel
    and BOTH backward kernels (one copy, so forward and backward can
    never disagree): skip future cells (and, under a sliding window,
    cells strictly before every row's window) entirely; mask only the
    cells straddling the diagonal or the window edge."""
    if not causal:
        return True, False
    live = ik * bk <= iq * bq + bq - 1
    diag = (ik * bk + bk - 1 > iq * bq) & live
    if window is not None:
        live = live & (ik * bk + bk - 1 > iq * bq - window)
        wedge = ik * bk < iq * bq + bq - window
        diag = (diag | wedge) & live
    return live, diag


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l2_ref, dvec_ref,
                         dq_ref, acc, *, causal, bq, bk, nk, nk_total,
                         mxu_dtype, inv_scale_a, chunk_k, window=None):
    """dQ cell: accumulate ds @ K over the k blocks of one q block.
    Each cell runs as an UNROLLED run of chunk_k sub-chunks — the same
    MXU/VPU pipelining lever as the forward fold: chunk c's exp2/ds VPU
    work has no dependence on chunk c+1's matmuls, and the per-chunk
    partial dq contributions are additive."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    j = pl.program_id(2)
    # under a sliding window the k dimension is bounded exactly like
    # the forward grid: j counts the O(window) visible blocks from the
    # shifted base, and ik is the REAL k-block index
    ik = j + (_window_first_block(iq, bq, bk, window)
              if window is not None else 0)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    live, diag = _grid_live_masked(iq, ik, bq, bk, causal, window)
    if window is not None:
        # phantom cells past the REAL k range (the bounded span's tail
        # with a clamped fetch) must stay dead regardless of the
        # causal/window algebra
        live = live & (ik < nk_total)
        diag = diag & live

    def body(masked):
        q2 = q_ref[0].astype(mxu_dtype)      # pre-scaled on the host
        do = do_ref[0].astype(mxu_dtype)
        l2 = l2_ref[0]
        dvec = dvec_ref[0]
        total = acc[:]
        for c in range(bk // chunk_k):
            kb = k_ref[0, pl.ds(c * chunk_k, chunk_k), :].astype(mxu_dtype)
            vb = v_ref[0, pl.ds(c * chunk_k, chunk_k), :].astype(mxu_dtype)
            p = _flash_bwd_p_block(q2, kb, l2, iq * bq,
                                   ik * bk + c * chunk_k, masked,
                                   window)
            dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - dvec)
            total = total + jax.lax.dot_general(
                ds.astype(mxu_dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc[:] = total

    if causal:
        @pl.when(diag)
        def _diag_body():
            body(masked=True)

        @pl.when(live & jnp.logical_not(diag))
        def _past_body():
            body(masked=False)
    else:
        body(masked=False)

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[0] = (acc[:] * inv_scale_a).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l2_ref, dvec_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, causal, bq,
                          bk, nq, nq_total, mxu_dtype, chunk_q,
                          window=None, group=1):
    """dK/dV cell: accumulate over the q blocks of one k block.  The
    q block is processed as an UNROLLED run of chunk_q sub-chunks (the
    roles of q and k swap relative to the dq kernel, so here the chunk
    axis is q) — independent sub-chunks whose partial dK/dV
    contributions are additive, giving Mosaic MXU/VPU overlap.

    GQA (``group`` > 1): the accumulation axis spans group * nq steps —
    every q head of this K/V head's group folds its contribution into
    the SAME dk/dv accumulators (the in-kernel transpose of the
    forward's zero-copy row sharing), so K/V never expand and no
    group-sum pass runs outside the kernel.  The q-side index maps pick
    (q head, q block) = divmod(j, nq); the mask algebra only needs the
    q-BLOCK index since every q head shares the same positions."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)
    j = pl.program_id(2)
    j2 = j % nq if group > 1 else j
    # bounded q iteration under a window: the q blocks that can see
    # k-block ik start at the causal lower bound (ik*bk)//bq and end
    # O(window) blocks later; j2 counts from that base
    iq = j2 + ((ik * bk) // bq if window is not None else 0)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live, diag = _grid_live_masked(iq, ik, bq, bk, causal, window)
    if window is not None:
        # CRITICAL: phantom cells past the REAL q range are causally
        # LIVE (future q rows attend past k columns), and their clamped
        # q fetches would accumulate garbage under wrong mask offsets —
        # bound liveness by the real grid
        live = live & (iq < nq_total)
        diag = diag & live

    def body(masked):
        kb = k_ref[0].astype(mxu_dtype)
        vb = v_ref[0].astype(mxu_dtype)
        dk_tot, dv_tot = dk_acc[:], dv_acc[:]
        for c in range(bq // chunk_q):
            sl = pl.ds(c * chunk_q, chunk_q)
            q2 = q_ref[0, sl, :].astype(mxu_dtype)
            do = do_ref[0, sl, :].astype(mxu_dtype)
            p = _flash_bwd_p_block(q2, kb, l2_ref[0, sl, :],
                                   iq * bq + c * chunk_q, ik * bk,
                                   masked, window)
            pc = p.astype(mxu_dtype)
            dv_tot = dv_tot + jax.lax.dot_general(
                pc, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - dvec_ref[0, sl, :])).astype(mxu_dtype)
            dk_tot = dk_tot + jax.lax.dot_general(
                ds, q2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_acc[:] = dk_tot
        dv_acc[:] = dv_tot

    if causal:
        @pl.when(diag)
        def _diag_body():
            body(masked=True)

        @pl.when(live & jnp.logical_not(diag))
        def _past_body():
            body(masked=False)
    else:
        body(masked=False)

    @pl.when(j == nq * group - 1)
    def _fin():
        # q2 carries the a*log2e prescale, so dK needs it divided back
        # out on top of its own `a` factor: a / (a*log2e) = 1/log2e
        dk_ref[0] = (dk_acc[:] * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(qp, kp, vp, out, lse, g_out, g_lse, cfg):
    from jax.experimental import pallas as pl

    (causal, bq, bk, ck, interpret, mxu_dtype, _kernel, _nc, _qt,
     _fd, window, _sm, kvg) = cfg
    N, T, D = qp.shape
    Tk = kp.shape[1]
    G = kvg if kvg else 1          # q heads per K/V head (GQA group)
    Nk = N // G                    # kp/vp rows: [Nk, Tk, D], grouped
    nq, nk = T // bq, Tk // bk
    a = 1.0 / float(D) ** 0.5
    # sub-chunk widths for the unrolled backward cells (the forward's
    # MXU/VPU pipelining lever): ck arrives resolved from the forward
    # call and already divides bk — dq chunks over k at ck directly;
    # dkv chunks over q, re-snapped against bq
    ckb = ck
    ckq = _snap_chunk(ck, bq)
    vma = _vma_of(qp, kp, vp, g_out)

    # host-side prep: prescaled q (exp2 domain), log2-domain lse, and
    # the dS offset with the lse cotangent folded in
    q2 = (qp.astype(jnp.float32) * (a * _LOG2E)).astype(qp.dtype)
    l2 = (lse * _LOG2E)[..., None]                       # [N, T, 1]
    dvec = jnp.sum(g_out.astype(jnp.float32)
                   * out.astype(jnp.float32), axis=-1, keepdims=True)
    if g_lse is not None:
        dvec = dvec - g_lse.astype(jnp.float32)[..., None]

    # under a sliding window both backward grids are BOUNDED like the
    # forward: the k dimension of dq spans only the O(window) visible
    # blocks from each q block's shifted base, and the q dimension of
    # dkv spans only the O(window) q blocks that can see each k block
    # (clamped fetches belong to dead, predicated-off cells)
    if window is not None:
        nk_eff = min(nk, (window - 1 + bq + bk - 1) // bk + 1)
        nq_eff = min(nq, (bk + window - 2) // bq + 2)

        def _kblk(i, j):
            return jnp.minimum(
                _window_first_block(i, bq, bk, window) + j, nk - 1)

        def _qblk(jk, j2):
            return jnp.minimum((jk * bk) // bq + j2, nq - 1)
    else:
        nk_eff, nq_eff = nk, nq

        def _kblk(i, j):
            return j

        def _qblk(jk, j2):
            return j2

    qb_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                           memory_space=pltpu.VMEM)
    # GQA: q row b reads K/V row b // G — the same zero-copy row
    # sharing as the forward's index maps; no expanded K/V exists
    kb_spec = pl.BlockSpec((1, bk, D),
                           lambda b, i, j: (b // G, _kblk(i, j), 0),
                           memory_space=pltpu.VMEM)
    ql_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                           memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, bq=bq,
                          bk=bk, nk=nk_eff, nk_total=nk,
                          mxu_dtype=mxu_dtype,
                          inv_scale_a=a, chunk_k=ckb, window=window),
        out_shape=_sds((N, T, D), qp.dtype, vma),
        grid=(N, nq, nk_eff),
        in_specs=[qb_spec, kb_spec, kb_spec, qb_spec, ql_spec, ql_spec],
        out_specs=qb_spec,
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q2, kp, vp, g_out, l2, dvec)

    # dK/dV: swap the roles — k blocks on the parallel axis, q blocks
    # accumulated sequentially.  Under GQA the sequential axis spans
    # the WHOLE q-head group (G * nq_eff steps): q row = b*G + i//nq,
    # q block = i%nq — each K/V head's dk/dv fold their group's
    # contributions in-kernel, expansion-free (ADVICE r4: the old path
    # repeated K/V G x and group-summed outside, scaling backward HBM
    # with the full q-head count)
    def _qrow(b, i):
        return b * G + i // nq_eff if G > 1 else b

    def _qblk2(jk, i):
        return _qblk(jk, i % nq_eff) if G > 1 else _qblk(jk, i)

    qs_spec = pl.BlockSpec((1, bq, D),
                           lambda b, jk, i: (_qrow(b, i), _qblk2(jk, i),
                                             0),
                           memory_space=pltpu.VMEM)
    ks_spec = pl.BlockSpec((1, bk, D), lambda b, jk, i: (b, jk, 0),
                           memory_space=pltpu.VMEM)
    ls_spec = pl.BlockSpec((1, bq, 1),
                           lambda b, jk, i: (_qrow(b, i), _qblk2(jk, i),
                                             0),
                           memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, bq=bq,
                          bk=bk, nq=nq_eff, nq_total=nq,
                          mxu_dtype=mxu_dtype,
                          chunk_q=ckq, window=window, group=G),
        out_shape=(_sds((Nk, Tk, D), kp.dtype, vma),
                   _sds((Nk, Tk, D), vp.dtype, vma)),
        grid=(Nk, nk, nq_eff * G),
        in_specs=[qs_spec, ks_spec, ks_spec, qs_spec, ls_spec, ls_spec],
        out_specs=(ks_spec, ks_spec),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q2, kp, vp, g_out, l2, dvec)

    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_packed_diff(qp, kp, vp, cfg):
    return _flash_forward_impl(qp, kp, vp, cfg)


def _flash_diff_fwd(qp, kp, vp, cfg):
    # symbolic_zeros=True wraps primals in (value, perturbed) records
    qp, kp, vp = (getattr(x, "value", x) for x in (qp, kp, vp))
    out, lse = _flash_forward_impl(qp, kp, vp, cfg)
    return (out, lse), (qp, kp, vp, out, lse)


def _flash_diff_bwd(cfg, res, cts):
    from jax.custom_derivatives import SymbolicZero

    qp, kp, vp, out, lse = res
    g_out, g_lse = cts
    # callers that discard lse (most) get a SYMBOLIC zero cotangent —
    # skip the dvec subtract instead of materializing a zero [N, T]
    if isinstance(g_lse, SymbolicZero):
        g_lse = None
    if isinstance(g_out, SymbolicZero):  # lse-only losses (rare)
        g_out = jnp.zeros(out.shape, out.dtype)
    # GQA and plain share ONE path: _flash_backward reads grouped K/V
    # through b//G index maps (dq) and folds each group's dK/dV inside
    # the dkv kernel's extended accumulation axis — K/V are never
    # expanded and no group-sum pass runs outside (ADVICE r4; the
    # forward's zero-copy row sharing, transposed)
    return _flash_backward(qp, kp, vp, out, lse, g_out, g_lse, cfg)


_flash_packed_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd,
                          symbolic_zeros=True)


def _flash_call(q, k, v, causal, block_q, block_k, interpret, mxu_dtype,
                kernel, q_tiles=None, fuse_denom=None, window=None,
                static_max=None):
    """BTHD-layout wrapper: packs [B,T,H,D] -> [B*H,T,D] around the
    core call (one HBM transpose per operand direction; XLA hoists the
    K/V packs out of iteration loops — callers on the hot path should
    still prefer the packed entry points).  A lane-blocked in-place
    alternative (index maps picking each head's 128-aligned lane chunk
    of a [B,T,H*D] view) was measured SLOWER than these transposes on
    the r04 chip — the per-head 512-byte strided DMA costs more than
    the packs — so the wrapper deliberately stays on the packing path.

    GQA: k/v may carry FEWER heads than q ([B, Tk, G, D], H % G == 0) —
    each K/V head serves H/G consecutive q heads, expansion-free in
    the forward (see :func:`_flash_call_packed`).

    Returns (out [B,T,H,D], lse [B,H,T] f32)."""
    B, T, H, D = q.shape
    G = k.shape[2] if k.ndim == 4 else -1
    if (k.shape != v.shape or k.ndim != 4 or k.shape[0] != B
            or k.shape[3] != D or G <= 0 or H % G != 0):
        raise ValueError(f"k/v shape {k.shape}/{v.shape} incompatible "
                         f"with q {q.shape} (K/V heads must divide "
                         f"q heads for GQA)")

    def pack(x):  # [B, t, h, D] -> [B*h, t, D] (h = that tensor's heads)
        t, h = x.shape[1], x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, t, D)

    out, lse = _flash_call_packed(pack(q), pack(k), pack(v), causal,
                                  block_q, block_k, interpret, mxu_dtype,
                                  kernel, q_tiles=q_tiles,
                                  fuse_denom=fuse_denom, window=window,
                                  static_max=static_max)
    return (out.reshape(B, H, T, D).transpose(0, 2, 1, 3),
            lse.reshape(B, H, T))


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "mxu_dtype", "kernel",
                                    "q_tiles", "fuse_denom", "window",
                                    "static_max"))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 256,
                    block_k: int = 512, interpret: bool = False,
                    mxu_dtype=jnp.bfloat16, kernel: str = "auto",
                    q_tiles: int | None = None,
                    fuse_denom: bool | None = None,
                    window: int | None = None,
                    static_max: float | None = None):
    """q, k, v: [B, T, H, D] -> [B, T, H, D] (self-attention, optional
    causal mask).  T must be divisible by the (auto-shrunk) block sizes.

    `mxu_dtype` is the matmul input format (bf16 default — the MXU's
    native rate; accumulation is always f32).  Pass jnp.float32 for
    reference-exact numerics at ~1/4 the throughput.

    `kernel` selects the schedule: "resident" pins the whole K/V row in
    VMEM per batch-head (fetched once; best while it fits), "grid"
    streams K/V blocks per q-block (any T), "auto" picks by K/V size.
    `q_tiles` (any schedule) and `fuse_denom` (resident only) are the
    throughput options (see :func:`flash_attention_packed`); leaving
    both at None applies the tuned auto schedule (plain single fold
    chain; fused denominator where its ones column is lane-tile-free,
    e.g. D=64)."""
    out, _lse = _flash_call(q, k, v, causal, block_q, block_k, interpret,
                            mxu_dtype, kernel, q_tiles, fuse_denom,
                            window, static_max)
    return out


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "mxu_dtype", "kernel",
                                    "q_tiles", "fuse_denom", "window",
                                    "static_max"))
def flash_attention_lse(q, k, v, causal: bool = False, block_q: int = 256,
                        block_k: int = 512, interpret: bool = False,
                        mxu_dtype=jnp.bfloat16, kernel: str = "auto",
                        q_tiles: int | None = None,
                        fuse_denom: bool | None = None,
                        window: int | None = None,
                        static_max: float | None = None):
    """Like :func:`flash_attention` but also returns the log-sum-exp
    statistics: (out [B, T, H, D], lse [B, H, T] fp32).  Partial results
    over different K/V shards combine exactly via lse weighting — the
    cross-shard fold ring attention applies around the ICI ring."""
    return _flash_call(q, k, v, causal, block_q, block_k, interpret,
                       mxu_dtype, kernel, q_tiles, fuse_denom, window,
                       static_max)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "mxu_dtype", "kernel",
                                    "chunk_k", "kv_cast_scratch",
                                    "q_tiles", "fuse_denom", "window",
                                    "static_max"))
def flash_attention_packed(q, k, v, causal: bool = False,
                           block_q: int = 256, block_k: int = 512,
                           interpret: bool = False,
                           mxu_dtype=jnp.bfloat16, kernel: str = "auto",
                           chunk_k: int | None = None,
                           kv_cast_scratch: bool = False,
                           q_tiles: int | None = None,
                           fuse_denom: bool | None = None,
                           window: int | None = None,
                           static_max: float | None = None):
    """Zero-copy entry on HEAD-PACKED operands: q, k, v are [N, T, D]
    with N = batch x heads flattened (the splash-attention layout).
    Unlike the [B, T, H, D] wrapper this moves NO bytes outside the
    kernel — callers that keep activations packed (the transformer
    family does between its projections) get the kernel at full rate.
    Returns out [N, T, D].

    `q_tiles` (every schedule) splits each q block into that many
    independent sub-tiles whose folds interleave — MXU/VPU overlap
    across dependence chains; it snaps down to a valid 8-row-aligned
    split.  `fuse_denom` (resident only; dropped when "auto" lands on
    grid) rides the softmax row-sum on the PV matmul via a
    ones-extended V — one fewer VPU pass per fold, free where D pads
    to the same lane tile (D=64).  Leaving either at None applies the
    tuned AUTO schedule from the measured table at the top of this
    module: the plain single fold chain over whole K blocks, with the
    fused denominator exactly where its ones column is lane-tile-free;
    explicit values (incl. q_tiles=1 / fuse_denom=False) always win.

    `static_max` (OPT-IN; resident and grid schedules) pins the
    softmax shift to a
    constant instead of the running row max: the max reduction, shift
    clamp, alpha rescale and masked-p re-zero leave the VPU budget —
    the fold's measured bottleneck at D=128.  EXACT (same p/l ratios,
    same lse) whenever every scaled logit s = q.k * log2e/sqrt(D)
    stays within f32 exponent range of the pin: overflow at
    s > static_max + 127, underflow only for weights ~2^-149 below
    the pin (numerically irrelevant).  A pin of 40 covers |logits|
    up to ~27 nats — far beyond trained-model attention logits; it is
    NOT safe for adversarially scaled inputs, which is why the
    dynamic-max fold stays the default.  See the kernel docstrings."""
    out, _lse = _flash_call_packed(q, k, v, causal, block_q, block_k,
                                   interpret, mxu_dtype, kernel, chunk_k,
                                   kv_cast_scratch, q_tiles, fuse_denom,
                                   window, static_max)
    return out


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "mxu_dtype", "kernel",
                                    "chunk_k", "kv_cast_scratch",
                                    "q_tiles", "fuse_denom", "window",
                                    "static_max"))
def flash_attention_packed_lse(q, k, v, causal: bool = False,
                               block_q: int = 256, block_k: int = 512,
                               interpret: bool = False,
                               mxu_dtype=jnp.bfloat16, kernel: str = "auto",
                               chunk_k: int | None = None,
                               kv_cast_scratch: bool = False,
                               q_tiles: int | None = None,
                               fuse_denom: bool | None = None,
                               window: int | None = None,
                               static_max: float | None = None):
    """Head-packed [N, T, D] variant returning (out [N, T, D],
    lse [N, T] fp32) — the distributed callers' entry (ring attention
    folds shard partials via the lse)."""
    return _flash_call_packed(q, k, v, causal, block_q, block_k,
                              interpret, mxu_dtype, kernel, chunk_k,
                              kv_cast_scratch, q_tiles, fuse_denom,
                              window, static_max)
