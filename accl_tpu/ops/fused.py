"""Fused compute/communication — chunked, double-buffered ring pipelines
that hide wire time under the MXU (r18).

The reference demonstrates kernels streaming operands directly into the
collective engine without touching memory (vadd_put.cpp:23-86 + the
stream flags in the call ABI), and ACCL+ (arxiv 2312.11742) shows where
the headroom lives: overlap the transfer of chunk k+1 with the compute
consuming chunk k.  This module is that schedule on TPU, in three tiers:

1. ``chunked_ring_*`` — the driver's fused lane (``ACCL_FUSED=1`` /
   per-call ``fused=``).  The flat payload is split into C independent
   per-chunk ppermute chains; at every ring step all C permutes are
   issued before any fold, so XLA pipelines chunk k+1's wire hop under
   chunk k's reduce.  The fp32 fold order is exactly the Pallas ring's
   (``local + incoming``, chunk ``(my - 2 - step) % P`` at step ``step``)
   so the fused lane is BITWISE-identical to the unfused ring whenever
   the payload divides P*C.  With ``wire=(block, error_feedback)`` the
   r17 int8 quantize/dequantize runs INSIDE the same loop body — one
   requantize per hop per chunk, no separate whole-buffer pack/unpack
   pass, wire-form carry across the reduce-scatter/all-gather seam.

2. ``fused_matmul_allreduce(chunks=C)`` — allreduce-into-matmul: the
   ring reduce-scatter phase computes each local partial product
   just-in-time (the MXU produces the block being folded while the next
   block's ppermute is in flight), then the all-gather relays reduced
   product rows.  ``fused_expert_ffn`` is the same idea for the MoE
   all_to_all: the dispatch for capacity-chunk k+1 overlaps the expert
   FFN consuming chunk k.

3. ``fused_matmul_reduce_scatter_pallas`` — the hand-scheduled Pallas
   form: the per-hop partial matmul executes between ``rdma.start()``
   and ``rdma.wait()`` on the accumulator's remote copy, with the same
   double-buffered landing slots and ACK-window flow control as
   ops/ring.py.

Device tracing (r15): with ``ACCL_DEVICE_TRACE`` set the fused lanes
stamp one row per (step, chunk) slot using an OVERLAPPED logical clock —
slot i's transfer spans [2i, 2i+2] and its reduce spans [2i+2, 2i+4],
so xfer(i+1) exactly covers reduce(i), the way the pipelined schedule
executes.  The sequential ring's 3-phase clock (ops/ring.py
``_stamp_row``) has zero xfer/reduce overlap by construction, which is
what `attribution.device_overlap` and scripts/overlap_smoke.py compare.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from .quantized import dequantize_blockwise, quantize_blockwise
from .quantized import DEFAULT_BLOCK
from .ring import (
    DEVICE_TRACE_COLS,
    _emit_device_trace,
    _payload_nbytes,
    _interp,
    device_trace_enabled,
    rs_signals_ack,
    rs_waits_ack,
)

#: default pipeline depth of the fused lane — chunks per ring step;
#: 2 is the minimum that overlaps, 4 amortizes the per-chunk dispatch
DEFAULT_FUSED_CHUNKS = 4

#: env override, read once (None = not read yet) — the fused lane is
#: opt-in, but its chunk count must still be stable across rebuilds so
#: plan replays compile the same jaxpr
_FUSED_CHUNKS: Optional[int] = None


def fused_chunks() -> int:
    """The ``ACCL_FUSED_CHUNKS`` pipeline depth, cached at first use."""
    global _FUSED_CHUNKS
    if _FUSED_CHUNKS is None:
        try:
            _FUSED_CHUNKS = max(1, int(os.environ.get(
                "ACCL_FUSED_CHUNKS", str(DEFAULT_FUSED_CHUNKS))))
        except ValueError:
            _FUSED_CHUNKS = DEFAULT_FUSED_CHUNKS
    return _FUSED_CHUNKS


def _reset_fused_chunks_cache() -> None:
    """Test hook: force the next call to re-read the env."""
    global _FUSED_CHUNKS
    _FUSED_CHUNKS = None


def _pick_chunks(n: int, requested: Optional[int]) -> int:
    """Largest chunk count <= requested that divides n (>=1)."""
    c = max(1, min(requested or fused_chunks(), n))
    while n % c:
        c -= 1
    return c


def _pad_flat(x, length: int):
    if x.shape[0] == length:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((length - x.shape[0],) + x.shape[1:], x.dtype)])


def _fused_stamp_rows(P: int, C: int, idx, chunk_bytes: int,
                      slot0: int = 0):
    """One stamp row per (step, chunk) pipeline slot, DEVICE_TRACE_FIELDS
    order, with the overlapped clock: xfer(i) = [2i, 2i+2], reduce(i) =
    [2i+2, 2i+4] — slot i+1's wire hop covers slot i's fold.

    With C == 1 there is only one chain and nothing to pipeline
    against, so the rows carry the sequential 3-phase clock
    (ops/ring.py ``_stamp_row``): the device timeline then honestly
    reports zero xfer/reduce overlap — the A/B baseline
    ``attribution.device_overlap`` compares the fused lanes to."""
    steps = (P - 1) * C
    slots = slot0 + jnp.arange(steps, dtype=jnp.int32)
    my = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (steps,))
    right = (my + 1) % P
    left = (my + P - 1) % P
    nbytes = jnp.full((steps,), jnp.int32(chunk_bytes))
    if C == 1:
        send, wait, phase = 3 * slots, 3 * slots + 1, 3 * slots + 2
    else:
        send, wait, phase = 2 * slots, 2 * slots + 2, 2 * slots + 4
    return jnp.stack(
        [my, slots, send, wait, phase, right, left, nbytes, nbytes],
        axis=1)


def _wire_hop_bytes(m: int, block: int) -> int:
    """Per-hop wire bytes of one int8 sub-chunk: int8 payload + one fp32
    scale per block (quantize_blockwise pads m up to a block multiple)."""
    rows = -(-m // block)
    return rows * block + rows * 4


# ---------------------------------------------------------------------------
# tier 1: chunked ring collectives — the driver's fused lane
# ---------------------------------------------------------------------------
def _rs_chains_fp(view, axis: str, op: str, P: int, C: int, idx, fwd):
    """C parallel reduce-scatter chains over `view` [P, C, m]; returns
    the list of per-chunk reduced accumulators.  All C permutes of a
    step are issued before any fold — the pipeline XLA overlaps."""
    is_max = op == "max"
    accs = [view[(idx - 1) % P, c] for c in range(C)]
    for s in range(P - 1):
        landed = [lax.ppermute(a, axis, fwd) for a in accs]
        jc = (idx - 2 - s) % P
        if is_max:
            accs = [jnp.maximum(view[jc, c], landed[c]) for c in range(C)]
        else:
            # local + incoming: the Pallas ring's fold order
            # (ring_reduce_scatter_pallas acc[...] = acc + landing)
            accs = [view[jc, c] + landed[c] for c in range(C)]
    return accs


def _rs_chains_q(view, axis: str, P: int, C: int, m: int, idx, fwd,
                 block: int, error_feedback: bool):
    """C parallel QUANTIZED reduce-scatter chains: the r17 int8 wire
    algebra (ops/quantized.py _ring_reduce_scatter_q) with the
    quantize/dequantize folded into the per-chunk loop body — each hop
    requantizes one sub-chunk, never the whole buffer.  Returns the list
    of wire-form (q, scale) carries (the seam feed for the gather)."""
    carries = []
    for c in range(C):
        x0 = view[(idx - 1) % P, c]
        q0, s0, _ = quantize_blockwise(x0, block)
        e0 = (x0 - dequantize_blockwise(q0, s0, m)) if error_feedback \
            else None
        carries.append((q0, s0, e0))
    for s in range(P - 1):
        moved = [(lax.ppermute(q, axis, fwd), lax.ppermute(sc, axis, fwd))
                 for (q, sc, _e) in carries]
        jc = (idx - 2 - s) % P
        nxt = []
        for c in range(C):
            q, sc = moved[c]
            err = carries[c][2]
            acc = dequantize_blockwise(q, sc, m) + view[jc, c]
            if error_feedback:
                acc = acc + err
            qn, scn, _ = quantize_blockwise(acc, block)
            en = (acc - dequantize_blockwise(qn, scn, m)) \
                if error_feedback else None
            nxt.append((qn, scn, en))
        carries = nxt
    return [(q, sc) for (q, sc, _e) in carries]


def _ag_chains(parts, axis: str, P: int, idx, fwd):
    """C parallel all-gather chains: relay each per-chunk part [m?]
    around the ring; returns [P, C, ...] with origin-major placement."""
    C = len(parts)
    stacked = jnp.stack(parts)  # [C, ...]
    outs = jnp.zeros((P,) + stacked.shape, stacked.dtype).at[idx].set(
        stacked)
    carries = list(parts)
    for s in range(P - 1):
        carries = [lax.ppermute(cc, axis, fwd) for cc in carries]
        origin = (idx - 1 - s) % P
        for c in range(C):
            outs = outs.at[origin, c].set(carries[c])
    return outs


def chunked_ring_reduce_scatter(x, axis: str = "rank", op: str = "sum",
                                chunks: Optional[int] = None,
                                wire: Optional[tuple] = None,
                                collective: str = "fused_reduce_scatter"):
    """Flat per-member [P * n] -> this member's reduced [n], pipelined
    as C independent per-chunk ring chains.  fp32 fold order matches the
    Pallas ring bitwise; ``wire=(block, error_feedback)`` rides the r17
    int8 wire with per-hop requantization fused into the loop."""
    P = lax.axis_size(axis)
    if P == 1:
        return x
    N = x.shape[0]
    if N % P:
        raise ValueError(f"fused reduce-scatter needs x.shape[0] ({N}) "
                         f"divisible by the '{axis}' axis size ({P})")
    n = N // P
    C = _pick_chunks(n, chunks)
    m = n // C
    idx = lax.axis_index(axis)
    fwd = [(i, (i + 1) % P) for i in range(P)]
    if wire is not None:
        if op == "max":
            raise ValueError("int8 wire lane carries sums, not max")
        block, ef = wire
        view = x.astype(jnp.float32).reshape(P, C, m)
        carries = _rs_chains_q(view, axis, P, C, m, idx, fwd, block, ef)
        parts = [dequantize_blockwise(q, sc, m) for q, sc in carries]
        hop_bytes = _wire_hop_bytes(m, block)
    else:
        view = x.reshape(P, C, m)
        parts = _rs_chains_fp(view, axis, op, P, C, idx, fwd)
        hop_bytes = _payload_nbytes((m,), x.dtype)
    if device_trace_enabled():
        _emit_device_trace(collective,
                           _fused_stamp_rows(P, C, idx, hop_bytes))
    return parts[0] if C == 1 else jnp.concatenate(parts)


def chunked_ring_all_gather(x, axis: str = "rank",
                            chunks: Optional[int] = None,
                            wire: Optional[tuple] = None,
                            collective: str = "fused_all_gather"):
    """Flat per-member [n] -> [P * n] (rank-major), pipelined as C
    per-chunk relay chains.  Values are relayed unchanged (fp) or
    quantized ONCE and relayed in wire form (int8 lane) — a single
    round-trip error regardless of P, as in r17."""
    P = lax.axis_size(axis)
    if P == 1:
        return x
    n = x.shape[0]
    C = _pick_chunks(n, chunks)
    m = n // C
    idx = lax.axis_index(axis)
    fwd = [(i, (i + 1) % P) for i in range(P)]
    if wire is not None:
        block = wire[0]
        view = x.astype(jnp.float32).reshape(C, m)
        qs = [quantize_blockwise(view[c], block)[:2] for c in range(C)]
        out_q = _ag_chains([q for q, _ in qs], axis, P, idx, fwd)
        out_s = _ag_chains([s for _, s in qs], axis, P, idx, fwd)
        deq = out_q.astype(jnp.float32) * out_s  # [P, C, rows, block]
        out = deq.reshape(P, C, -1)[:, :, :m].reshape(-1)
        hop_bytes = _wire_hop_bytes(m, block)
    else:
        view = x.reshape(C, m)
        out = _ag_chains([view[c] for c in range(C)], axis, P, idx,
                         fwd).reshape(-1)
        hop_bytes = _payload_nbytes((m,), x.dtype)
    if device_trace_enabled():
        _emit_device_trace(collective,
                           _fused_stamp_rows(P, C, idx, hop_bytes))
    return out


def chunked_ring_all_reduce(x, axis: str = "rank", op: str = "sum",
                            chunks: Optional[int] = None,
                            wire: Optional[tuple] = None,
                            collective: str = "fused_allreduce"):
    """Flat per-member [N] -> [N] allreduced: chunked reduce-scatter
    feeding chunked all-gather.  Pads internally to a P*C multiple; on
    the int8 lane the wire-form carry crosses the phase seam without a
    dequant/requant round (r17 invariant, now per chunk)."""
    P = lax.axis_size(axis)
    if P == 1:
        return x
    N = x.shape[0]
    C = max(1, chunks or fused_chunks())
    padN = -(-N // (P * C)) * (P * C)
    xp = _pad_flat(x, padN)
    n = padN // P
    m = n // C
    idx = lax.axis_index(axis)
    fwd = [(i, (i + 1) % P) for i in range(P)]
    if wire is not None:
        if op == "max":
            raise ValueError("int8 wire lane carries sums, not max")
        block, ef = wire
        view = xp.astype(jnp.float32).reshape(P, C, m)
        carries = _rs_chains_q(view, axis, P, C, m, idx, fwd, block, ef)
        out_q = _ag_chains([q for q, _ in carries], axis, P, idx, fwd)
        out_s = _ag_chains([s for _, s in carries], axis, P, idx, fwd)
        deq = out_q.astype(jnp.float32) * out_s
        out = deq.reshape(P, C, -1)[:, :, :m].reshape(-1)[:N]
        out = out.astype(x.dtype) if jnp.issubdtype(x.dtype, jnp.floating) \
            else out
        hop_bytes = _wire_hop_bytes(m, block)
    else:
        view = xp.reshape(P, C, m)
        parts = _rs_chains_fp(view, axis, op, P, C, idx, fwd)
        out = _ag_chains(parts, axis, P, idx, fwd).reshape(-1)[:N]
        hop_bytes = _payload_nbytes((m,), x.dtype)
    if device_trace_enabled():
        rows = jnp.concatenate([
            _fused_stamp_rows(P, C, idx, hop_bytes, slot0=0),
            _fused_stamp_rows(P, C, idx, hop_bytes, slot0=(P - 1) * C),
        ])
        _emit_device_trace(collective, rows)
    return out


# ---------------------------------------------------------------------------
# tier 2: allreduce-into-matmul and MoE dispatch fusion
# ---------------------------------------------------------------------------
def _matmul_kernel(x_ref, w_ref, o_ref):
    o_ref[:] = jnp.dot(x_ref[:], w_ref[:],
                       preferred_element_type=jnp.float32)


def pallas_matmul(x, w, block_m: int = 256, block_n: int = 256,
                  interpret: bool = False):
    """Tiled MXU matmul (the compute half of the fusion).  Shapes must be
    multiples of the MXU tile (128) for peak efficiency."""
    from jax.experimental import pallas as pl

    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn = min(block_m, m), min(block_n, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, bn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(m * k + k * n + m * n) * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(x, w)


def fused_matmul_allreduce(x, w, axis: str = "tp", use_pallas: bool = True,
                           interpret: bool = False,
                           chunks: Optional[int] = None):
    """Tensor-parallel contraction: each member holds a K-shard of the
    weight; the partial products all-reduce over the `axis` ring.

    With ``chunks=None`` (the default) this is the r2 form — one matmul
    and a psum, XLA overlapping the tail.  With ``chunks=C`` it becomes
    the pipelined allreduce-into-matmul: the reduce-scatter phase
    computes each local row-block partial JUST-IN-TIME (the MXU produces
    the block being folded while the next block's ppermute is in
    flight), then the all-gather relays the reduced product rows.  Rows
    are zero-padded to a P*C multiple internally; output is fp32 either
    way."""
    if chunks is None or chunks <= 1:
        partial_out = (pallas_matmul(x, w, interpret=interpret)
                       if use_pallas else
                       jnp.dot(x, w, preferred_element_type=jnp.float32))
        return lax.psum(partial_out, axis)

    P = lax.axis_size(axis)
    if P == 1:
        return (pallas_matmul(x, w, interpret=interpret) if use_pallas
                else jnp.dot(x, w, preferred_element_type=jnp.float32))
    M, K = x.shape
    N = w.shape[1]
    C = chunks
    padM = -(-M // (P * C)) * (P * C)
    xp = _pad_flat(x, padM)
    mrows = padM // (P * C)
    xv = xp.reshape(P, C, mrows, K)
    idx = lax.axis_index(axis)
    fwd = [(i, (i + 1) % P) for i in range(P)]

    def dot_block(a):
        if use_pallas:
            return pallas_matmul(a, w, interpret=interpret)
        return jnp.dot(a, w, preferred_element_type=jnp.float32)

    # reduce-scatter of the product, local partial computed per hop —
    # the ppermute for chunk k+1 is independent of chunk k's matmul+fold
    accs = [dot_block(xv[(idx - 1) % P, c]) for c in range(C)]
    for s in range(P - 1):
        landed = [lax.ppermute(a, axis, fwd) for a in accs]
        jc = (idx - 2 - s) % P
        accs = [dot_block(xv[jc, c]) + landed[c] for c in range(C)]
    out = _ag_chains(accs, axis, P, idx, fwd).reshape(padM, N)[:M]
    if device_trace_enabled():
        hop_bytes = mrows * N * 4
        rows = jnp.concatenate([
            _fused_stamp_rows(P, C, idx, hop_bytes, slot0=0),
            _fused_stamp_rows(P, C, idx, hop_bytes, slot0=(P - 1) * C),
        ])
        _emit_device_trace("fused_matmul_allreduce", rows)
    return out


def fused_expert_ffn(x, expert_idx, ffn: Callable, axis: str = "ep",
                     capacity: int = 0, chunks: Optional[int] = None):
    """Reduce-scatter-into-MoE-dispatch: route tokens to their expert and
    run the expert FFN with the capacity dimension split into C chunks,
    so the all_to_all for chunk k+1 is in flight while ``ffn`` consumes
    chunk k (and the return all_to_all for chunk k overlaps chunk k+1's
    FFN).  Same slotting/capacity semantics as
    parallel.strategies.expert_dispatch/expert_combine; ``ffn`` maps
    [T, D] -> [T, D] row-wise (the per-expert MLP)."""
    P = lax.axis_size(axis)
    N, D = x.shape
    cap = capacity or -(-N // P)
    C = _pick_chunks(cap, chunks)
    ck = cap // C
    onehot = jax.nn.one_hot(expert_idx, P, dtype=jnp.int32)  # [N, P]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot - 1
    slot = jnp.sum(pos_in_expert * onehot, axis=1)  # [N]
    keep = slot < cap
    buckets = jnp.zeros((P, cap, D), x.dtype)
    buckets = buckets.at[expert_idx, jnp.clip(slot, 0, cap - 1)].add(
        jnp.where(keep[:, None], x, 0.0))
    back_parts = []
    for c in range(C):
        b = lax.dynamic_slice_in_dim(buckets, c * ck, ck, axis=1)
        recv = lax.all_to_all(b, axis, split_axis=0, concat_axis=0,
                              tiled=False)  # [P, ck, D]
        y = ffn(recv.reshape(P * ck, D))
        back_parts.append(
            lax.all_to_all(y.reshape(P, ck, D), axis, split_axis=0,
                           concat_axis=0, tiled=False))
    back = jnp.concatenate(back_parts, axis=1)  # [P, cap, D]
    if device_trace_enabled():
        idx = lax.axis_index(axis)
        hop_bytes = _payload_nbytes((ck, D), x.dtype)
        _emit_device_trace(
            "fused_expert_ffn",
            _fused_stamp_rows(P, C, idx, hop_bytes))
    gathered = back[expert_idx, jnp.clip(slot, 0, cap - 1)]
    return jnp.where(keep[:, None], gathered, 0.0)


# ---------------------------------------------------------------------------
# tier 3: the hand-scheduled Pallas kernel — per-hop matmul between
# rdma.start() and rdma.wait() on the accumulator's remote copy
# ---------------------------------------------------------------------------
def fused_matmul_reduce_scatter_pallas(x, w, axis: str = "rank",
                                       interpret: bool = False,
                                       collective_id: int = 1):
    """Ring reduce-scatter of the partial products sum_r x_r @ w_r with
    the matmul INSIDE the ring loop: x [P, m, K] per member (P row-blocks
    of this member's activations against its K-shard w [K, N]); returns
    this member's reduced [m, N] product block.

    Schedule per hop (vs ring_reduce_scatter_pallas, which idles between
    ``rdma.start()`` and ``rdma.wait()``): start the accumulator's
    remote copy, compute the NEXT local partial on the MXU while the DMA
    flies, then wait and fold.  Same double-buffered landing slots and
    ACK-window flow control; stamp rows use the overlapped clock."""
    from jax.experimental import pallas as pl

    P = lax.axis_size(axis)
    if P == 1:
        return jnp.dot(x[0], w, preferred_element_type=jnp.float32)
    V, m, K = x.shape
    if V != P:
        raise ValueError(f"x leading dim ({V}) must equal the '{axis}' "
                         f"axis size ({P})")
    N = w.shape[1]
    out_block = (m, N)
    devtrace = device_trace_enabled()
    chunk_bytes = _payload_nbytes(out_block, jnp.float32)

    def kernel(x_ref, w_ref, out_ref, *rest):
        if devtrace:
            trace_ref, wv, xa, acc, landing, send_sem, recv_sem, \
                ack_sem, copy_sem = rest
        else:
            wv, xa, acc, landing, send_sem, recv_sem, ack_sem, \
                copy_sem = rest
        my = lax.axis_index(axis)
        right = (my + 1) % P
        left = (my + P - 1) % P

        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

        lw = pltpu.make_async_copy(w_ref, wv, copy_sem)
        lw.start()
        lw.wait()
        # acc starts as our partial for chunk (my - 1): the first
        # payload forwarded (ring_reduce_scatter_pallas's `first`)
        first = (my + P - 1) % P
        ld = pltpu.make_async_copy(x_ref.at[first], xa, copy_sem)
        ld.start()
        ld.wait()
        acc[...] = jnp.dot(xa[...], wv[...],
                           preferred_element_type=jnp.float32)

        for step in range(P - 1):
            slot = step % 2
            if rs_waits_ack(step, P):
                pltpu.semaphore_wait(ack_sem.at[slot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=acc,
                dst_ref=landing.at[slot],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            # MXU work under the wire: the local partial for the chunk
            # about to be folded, computed while the DMA is in flight
            cidx = (my - 2 - step) % P
            ld2 = pltpu.make_async_copy(x_ref.at[cidx], xa, copy_sem)
            ld2.start()
            ld2.wait()
            prod = jnp.dot(xa[...], wv[...],
                           preferred_element_type=jnp.float32)
            rdma.wait()
            acc[...] = prod + landing[slot]
            if rs_signals_ack(step, P):
                pltpu.semaphore_signal(
                    ack_sem.at[slot], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            if devtrace:
                # overlapped clock: this hop's wire spans [2s, 2s+2],
                # its fold [2s+2, 2s+4] — xfer(s+1) covers reduce(s)
                trace_ref[step, :] = jnp.stack([
                    jnp.asarray(my, jnp.int32),
                    jnp.int32(step),
                    jnp.int32(2 * step),
                    jnp.int32(2 * step + 2),
                    jnp.int32(2 * step + 4),
                    jnp.asarray(right, jnp.int32),
                    jnp.asarray(left, jnp.int32),
                    jnp.int32(chunk_bytes),
                    jnp.int32(chunk_bytes),
                ])

        st = pltpu.make_async_copy(acc, out_ref, copy_sem)
        st.start()
        st.wait()

    out_shape: Any = jax.ShapeDtypeStruct(out_block, jnp.float32)
    out_specs: Any = pl.BlockSpec(memory_space=pl.ANY)
    if devtrace:
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (P - 1, DEVICE_TRACE_COLS), jnp.int32)]
        out_specs = [out_specs, pl.BlockSpec(memory_space=pltpu.SMEM)]
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((K, N), w.dtype),
            pltpu.VMEM((m, K), x.dtype),
            pltpu.VMEM(out_block, jnp.float32),
            pltpu.VMEM((2,) + out_block, jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp(interpret),
    )(x, w)
    if devtrace:
        out, tr = res
        _emit_device_trace("fused_matmul_reduce_scatter", tr)
        return out
    return res


#: scoped VMEM one fused-kernel tile may fill with its scratch: the
#: weight block, the activation block, the fp32 accumulator, its two
#: landing slots and the fp32 product (v5e's scoped limit is 16 MiB and
#: Mosaic keeps temporaries of its own beside these)
_FUSED_VMEM_BUDGET = 12 << 20


def _fused_tiles(m: int, K: int, N: int, xbytes: int,
                 wbytes: int) -> tuple:
    """(row block, column block) of the [m, N] product that one
    fused_matmul_reduce_scatter_pallas call computes: halve columns,
    then rows, until the tile's scratch fits _FUSED_VMEM_BUDGET."""
    bm, bn = m, N

    def need(bm, bn):
        return K * bn * wbytes + bm * K * xbytes + 4 * bm * bn * 4

    while need(bm, bn) > _FUSED_VMEM_BUDGET and bn % 256 == 0:
        bn //= 2
    while need(bm, bn) > _FUSED_VMEM_BUDGET and bm % 16 == 0:
        bm //= 2
    return bm, bn


def fused_matmul_allreduce_pallas(x, w, axis: str = "rank",
                                  interpret: bool = False):
    """Allreduce-into-matmul, Pallas form: allreduce(sum_r x @ w_r) for
    x [M, K] (M divisible by P) and K-shard w [K, N] — the fused
    reduce-scatter kernel computes and folds per-hop partials under the
    wire, then the ring all-gather relays the reduced product rows.
    The product is split into tiles whose scratch fits VMEM, one fused
    ring pass each."""
    from .ring import ring_all_gather_segmented

    P = lax.axis_size(axis)
    M, K = x.shape
    N = w.shape[1]
    if P == 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    if M % P:
        raise ValueError(f"M ({M}) must divide the '{axis}' axis size "
                         f"({P}); pad the row dimension")
    m = M // P
    bm, bn = _fused_tiles(m, K, N, x.dtype.itemsize, w.dtype.itemsize)
    # [row block, P, bm, K] and [col block, K, bn]: one fused ring pass
    # per (row, col) tile, scanned so the program holds one kernel
    xs = jnp.swapaxes(x.reshape(P, m // bm, bm, K), 0, 1)
    ws = jnp.swapaxes(w.reshape(K, N // bn, bn), 0, 1)

    def row(_, xr):
        def tile(_, wc):
            return None, fused_matmul_reduce_scatter_pallas(
                xr, wc, axis, interpret=interpret, collective_id=2)

        _, cols = lax.scan(tile, None, ws)  # [N // bn, bm, bn]
        return None, jnp.swapaxes(cols, 0, 1).reshape(bm, N)

    _, mine = lax.scan(row, None, xs)  # [m // bm, bm, N]
    gathered = ring_all_gather_segmented(mine.reshape(-1), axis,
                                         interpret=interpret)
    return gathered.reshape(M, N)
