"""Ring collectives as Pallas TPU kernels over inter-chip RDMA.

This is the firmware's ring schedule family (segmented ring allreduce
fw :1888-2071, ring allgather :1299-1505, ring reduce_scatter
:1748-1852) re-expressed the TPU way: `make_async_remote_copy` plays the
rendezvous one-sided RDMA WRITE (rdma_sq_handler.cpp:53-130), DMA
semaphores play the WR_DONE / address-exchange completions, and the
neighbor barrier plays session setup.  Double-buffered communication
slots give the 2-deep software pipelining the firmware gets from its
`end_move` windows.

All entry points must be called inside `shard_map` over a 1-D mesh axis
(ICI ring).  Chunk sizes must fit VMEM (~16 MB/core): callers segment
larger payloads exactly as the firmware segments to rx-buffer size.

On non-TPU platforms the kernels run under the Pallas TPU interpreter
(`interpret=True` → `pltpu.InterpretParams`) which simulates the remote
DMAs — the CPU rung of the test ladder.

Device tracing (r15): with ``ACCL_DEVICE_TRACE`` set, every ring
kernel writes one stamp row per step — logical phase stamps
(send-issue, recv/ack-wait done, reduce/copy done; Pallas exposes no
cycle counter, so stamps are event-order clocks) plus the two ring
neighbors and per-neighbor byte counts — into an extra kernel output
that a ``jax.debug.callback`` lands in the trace collector
(observability/trace.py ``device:<collective>`` Perfetto tracks).  The
env gate is read ONCE at first kernel build; with it unset the built
kernels are bit-identical to the uninstrumented ones (no extra output,
no callback — the jaxpr pin in tests/test_device_trace.py).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from ..observability.trace import DEVICE_TRACE_FIELDS, record_device_steps

#: stamp-row width of the ACCL_DEVICE_TRACE kernel output (the column
#: schema lives with its consumer: observability/trace.py)
DEVICE_TRACE_COLS = len(DEVICE_TRACE_FIELDS)

#: env gate, read once at first kernel build (None = not read yet) —
#: the off path must cost zero structurally, so the gate can never be
#: consulted per call beyond one module-global read
_DEVICE_TRACE: Optional[bool] = None


def device_trace_enabled() -> bool:
    """The ``ACCL_DEVICE_TRACE`` gate, cached at first kernel build."""
    global _DEVICE_TRACE
    if _DEVICE_TRACE is None:
        _DEVICE_TRACE = os.environ.get(
            "ACCL_DEVICE_TRACE", "0") not in ("", "0")
    return bool(_DEVICE_TRACE)


def _reset_device_trace_cache() -> None:
    """Test hook: force the next kernel build to re-read the env."""
    global _DEVICE_TRACE
    _DEVICE_TRACE = None


def _emit_device_trace(collective: str, buf: Any) -> None:
    """Arm the host callback that lands one stamp buffer in the trace
    collector (runs at execution time with the concrete array, inside
    jit/shard_map)."""
    jax.debug.callback(
        functools.partial(record_device_steps, collective), buf)


def _stamp_row(trace_ref: Any, step: int, my: Any, tx_peer: Any,
               rx_peer: Any, tx_bytes: int, rx_bytes: int) -> None:
    """Write one per-step stamp row (DEVICE_TRACE_FIELDS order).  The
    three phase stamps are the logical event clock 3*step + {0,1,2}:
    send-issue, recv/ack-wait done, reduce/copy done."""
    seq = 3 * step
    trace_ref[step, :] = jnp.stack([
        jnp.asarray(my, jnp.int32),
        jnp.int32(step),
        jnp.int32(seq),
        jnp.int32(seq + 1),
        jnp.int32(seq + 2),
        jnp.asarray(tx_peer, jnp.int32),
        jnp.asarray(rx_peer, jnp.int32),
        jnp.int32(tx_bytes),
        jnp.int32(rx_bytes),
    ])


def _payload_nbytes(shape: tuple, dtype: Any) -> int:
    """Bytes of one chunk of `shape`/`dtype` — the per-hop tx/rx byte
    count the stamp rows carry (a Python int at kernel-build time)."""
    n = int(np.dtype(dtype).itemsize)
    for d in shape:
        n *= int(d)
    return n


def _interp(interpret: bool):
    """The `interpret` argument of a remote-DMA kernel's pallas_call:
    the TPU interpreter simulates the remote copies and semaphores."""
    if not interpret:
        return False

    return pltpu.InterpretParams()


# ---------------------------------------------------------------------------
# Flow-control window algebra — shared by the kernels below and by the
# discrete-event replay in tests/test_ring_flowcontrol.py, which runs
# the schedule under adversarial delivery and fails on any off-by-one
# (double-buffer overrun, deadlock, or semaphore-ledger leak) BEFORE it
# can deadlock real hardware.  The CPU interpreter serializes
# rdma.start();rdma.wait() and can never provoke these races itself.
#
# All-gather: comm slot parity flips every step; the slot we will land
# the NEXT incoming chunk in was last read by our own forwarding send
# one step ago, so from step 1 on we must hold the left neighbor off
# until we ACK, and we ACK a slot as soon as our send out of it
# completes — except the last two steps, whose slots are never written
# again (fw RAW hazard :1457-1460).
def ag_waits_ack(step: int, P: int) -> bool:
    return step >= 1


def ag_signals_ack(step: int, P: int) -> bool:
    return step <= P - 3


# Reduce-scatter: the landing buffer (not the accumulator) is double-
# buffered; a slot is reusable after the fold that consumed it, two
# steps after it was written.
def rs_waits_ack(step: int, P: int) -> bool:
    return step >= 2


def rs_signals_ack(step: int, P: int) -> bool:
    return step <= P - 4


def ring_all_gather_pallas(x, axis: str = "rank", interpret: bool = False,
                           collective_id: int = 0,
                           ring_size: int | None = None):
    """All-gather over a ring: per-member [n, ...] → [P, n, ...].

    Pattern: local slot write, then P-1 hops; each hop remote-copies the
    newest chunk to the right neighbor's double-buffered landing slot
    (the guide's canonical ring; fw eager allgather relay :1404-1502).

    ``ring_size`` (only with a 1-member axis) runs the kernel as a
    VIRTUAL V-rank self-ring on the single device: every hop is a real
    remote DMA (device_id = self) with the real semaphore handshakes
    and ACK-window flow control, so the compiled collective executes on
    one chip — the reference's run-the-synthesized-artifact rung
    (test/model/simulator/cclo_sim.cpp:57-559).  Since every virtual
    rank is this device, the result is x tiled V times (checkable).
    """
    from jax.experimental import pallas as pl

    P = lax.axis_size(axis)
    V = ring_size if ring_size is not None else P
    if V != P and P != 1:
        raise ValueError("ring_size override requires a 1-member axis "
                         f"(self-ring mode); got P={P}, ring_size={V}")
    if V == 1:
        return x[None]
    devtrace = device_trace_enabled()
    chunk_bytes = _payload_nbytes(x.shape, x.dtype)

    def kernel(x_ref, out_ref, *rest):
        if devtrace:
            trace_ref, comm_buf, send_sem, recv_sem, ack_sem, copy_sem \
                = rest
        else:
            comm_buf, send_sem, recv_sem, ack_sem, copy_sem = rest
        my = lax.axis_index(axis) % V
        right = (my + 1) % P

        # neighbor handshake so nobody's landing slot is written before
        # the kernel owns it (session-setup equivalent)
        barrier = pltpu.get_barrier_semaphore()
        left = (my + P - 1) % P
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

        # our own block: out[my] and the first send slot
        local_out = pltpu.make_async_copy(x_ref, out_ref.at[my], copy_sem)
        local_out.start()
        local_comm = pltpu.make_async_copy(x_ref, comm_buf.at[0], copy_sem)
        local_comm.start()
        local_out.wait()
        local_comm.wait()

        for step in range(V - 1):
            slot = step % 2
            nxt = (step + 1) % 2
            # flow control: the slot we are about to write on the right
            # neighbor was freed by its own send two steps ago — wait for
            # its consumption ACK so a fast ring segment can't overrun the
            # double buffer (the firmware's rx-buffer RAW hazard,
            # fw :1457-1460, solved with sequence windows there)
            if ag_waits_ack(step, V):
                pltpu.semaphore_wait(ack_sem.at[nxt], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=comm_buf.at[slot],
                dst_ref=comm_buf.at[nxt],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[nxt],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdma.wait()
            # our send of comm_buf[slot] is complete: that slot is free
            # for the left neighbor's next write into it
            if ag_signals_ack(step, V):
                pltpu.semaphore_signal(
                    ack_sem.at[slot], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            origin = (my - step - 1) % V
            put = pltpu.make_async_copy(comm_buf.at[nxt], out_ref.at[origin],
                                        copy_sem)
            put.start()
            put.wait()
            if devtrace:
                # per-step stamp row: each hop relays one chunk to the
                # right neighbor and lands one from the left
                _stamp_row(trace_ref, step, my, right, left,
                           chunk_bytes, chunk_bytes)

    out_shape: Any = jax.ShapeDtypeStruct((V,) + x.shape, x.dtype)
    out_specs: Any = pl.BlockSpec(memory_space=pl.ANY)
    if devtrace:
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (V - 1, DEVICE_TRACE_COLS), jnp.int32)]
        out_specs = [out_specs, pl.BlockSpec(memory_space=pltpu.SMEM)]
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2,) + x.shape, x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp(interpret),
    )(x)
    if devtrace:
        out, tr = res
        _emit_device_trace("all_gather", tr)
        return out
    return res


def ring_reduce_scatter_pallas(x, axis: str = "rank", op: str = "sum",
                               interpret: bool = False,
                               collective_id: int = 1,
                               ring_size: int | None = None):
    """Ring reduce-scatter: per-member [P, n, ...] → member's reduced
    [n, ...] (fw :1782-1850: send chunk (rank-1), P-2 fused
    recv+reduce+forward hops, final hop folds chunk `rank`).

    ``ring_size`` (1-member axis only): virtual V-rank self-ring on one
    device — real remote DMAs and semaphore flow control, every virtual
    rank being this device (see ring_all_gather_pallas).  The self-ring
    result is the full `op`-reduction of our own V chunks (each hop's
    incoming partial is our own accumulator)."""
    from jax.experimental import pallas as pl

    P = lax.axis_size(axis)
    V = ring_size if ring_size is not None else P
    if V != P and P != 1:
        raise ValueError("ring_size override requires a 1-member axis "
                         f"(self-ring mode); got P={P}, ring_size={V}")
    if V == 1:
        return x[0]
    chunk_shape = x.shape[1:]
    is_max = op == "max"
    devtrace = device_trace_enabled()
    chunk_bytes = _payload_nbytes(chunk_shape, x.dtype)

    def kernel(x_ref, out_ref, *rest):
        if devtrace:
            trace_ref, acc, landing, send_sem, recv_sem, ack_sem, \
                copy_sem = rest
        else:
            acc, landing, send_sem, recv_sem, ack_sem, copy_sem = rest
        my = lax.axis_index(axis) % V
        right = ((my + 1) % V) % P
        left = ((my + V - 1) % V) % P

        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

        # acc starts as our chunk (my - 1): the first payload forwarded
        first = (my + V - 1) % V
        ld = pltpu.make_async_copy(x_ref.at[first], acc, copy_sem)
        ld.start()
        ld.wait()

        for step in range(V - 1):
            slot = step % 2
            # flow control: the landing slot we target was consumed by
            # the right neighbor's fold two steps ago — wait for its ACK
            # so ring skew can't overrun the double buffer
            if rs_waits_ack(step, V):
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
            rdma.wait()
            # fold the arriving partial with our local copy of the chunk
            # now travelling: chunk (my - 2 - step) mod V
            cidx = (my - 2 - step) % V
            ld2 = pltpu.make_async_copy(x_ref.at[cidx], acc, copy_sem)
            ld2.start()
            ld2.wait()
            if is_max:
                acc[...] = jnp.maximum(acc[...], landing[slot])
            else:
                acc[...] = acc[...] + landing[slot]
            # landing[slot] consumed: free it for the left neighbor's
            # write at its step (step + 2)
            if rs_signals_ack(step, V):
                pltpu.semaphore_signal(
                    ack_sem.at[slot], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            if devtrace:
                # per-step stamp row: one partial forwarded right, one
                # landed from the left and folded into the accumulator
                _stamp_row(trace_ref, step, my, right, left,
                           chunk_bytes, chunk_bytes)

        st = pltpu.make_async_copy(acc, out_ref, copy_sem)
        st.start()
        st.wait()

    out_shape: Any = jax.ShapeDtypeStruct(chunk_shape, x.dtype)
    out_specs: Any = pl.BlockSpec(memory_space=pl.ANY)
    if devtrace:
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (V - 1, DEVICE_TRACE_COLS), jnp.int32)]
        out_specs = [out_specs, pl.BlockSpec(memory_space=pltpu.SMEM)]
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM(chunk_shape, x.dtype),
            pltpu.VMEM((2,) + chunk_shape, x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp(interpret),
    )(x)
    if devtrace:
        out, tr = res
        _emit_device_trace("reduce_scatter", tr)
        return out
    return res


def ring_all_reduce_pallas(x, axis: str = "rank", op: str = "sum",
                           interpret: bool = False, cid_rs: int = 1,
                           cid_ag: int = 0, ring_size: int | None = None):
    """Segmented ring allreduce = ring reduce-scatter + ring all-gather
    (fw :1888-2071).  Per-member x: [P * n, ...] → same shape, reduced.

    The two phases reuse the ring kernels.  ``ring_size`` propagates
    the single-device virtual self-ring mode (see
    ring_all_gather_pallas).
    """
    P = lax.axis_size(axis)
    V = ring_size if ring_size is not None else P
    if V != P and P != 1:
        raise ValueError("ring_size override requires a 1-member axis "
                         f"(self-ring mode); got P={P}, ring_size={V}")
    if V == 1:
        return x
    n = x.shape[0] // V
    chunks = x.reshape((V, n) + x.shape[1:])
    mine = ring_reduce_scatter_pallas(chunks, axis, op=op,
                                      interpret=interpret,
                                      collective_id=cid_rs,
                                      ring_size=ring_size)
    gathered = ring_all_gather_pallas(mine, axis, interpret=interpret,
                                      collective_id=cid_ag,
                                      ring_size=ring_size)
    return gathered.reshape(x.shape)


# ---------------------------------------------------------------------------
# segmentation drivers — the firmware's rx-buffer segmentation above the
# ring kernels (fw :1888-2071: chunk to rx-buf size, bulk/tail split for
# ragged payloads).  The payload is split evenly over the fewest
# segments that fit, each zero-padded to whole tiles (under one tile of
# padding per segment), and a lax.scan runs one ring pass per segment
# with the same collective ids, so the program holds one set
# of kernels whatever the payload size: an unrolled loop of 128 MiB in
# 1 MiB segments took minutes to compile on the chip's host, past the
# driver's call timeout.  Each chunk travels as a [rows, 128] block, so
# the kernels slice only untiled leading dimensions (Mosaic refuses a
# row slice of a 2-D VMEM buffer that is not tile-aligned).
# ---------------------------------------------------------------------------

#: lane width of a TPU vreg
LANES = 128

#: bytes one ring kernel moves per hop; its VMEM scratch holds two
#: (all-gather) or three (reduce-scatter) of these, well inside v5e's
#: 16 MiB scoped VMEM
RING_CHUNK_BYTES = 2 << 20


def _pad_to(x, length):
    if x.shape[0] == length:
        return x
    pad = jnp.zeros((length - x.shape[0],) + x.shape[1:], x.dtype)
    return jnp.concatenate([x, pad])


def _tile(dtype: Any) -> int:
    """Elements of one native [sublanes, 128] tile: 8 sublanes for
    32-bit, 16 for 16-bit, 32 for 8-bit dtypes."""
    return 8 * max(1, 4 // int(np.dtype(dtype).itemsize)) * LANES


def _seg_len(n: int, seg_max: int) -> int:
    """Payload elements per segment: `n` split evenly over the fewest
    segments of at most `seg_max`."""
    return -(-n // -(-n // seg_max))


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _segments(x, seg: int, width: int):
    """Flat [n] -> [nseg, width]: `seg` payload elements per segment,
    each zero-padded to `width` (less than one tile more)."""
    nseg = -(-x.shape[0] // seg)
    segs = _pad_to(x, nseg * seg).reshape(nseg, seg)
    return jnp.pad(segs, ((0, 0), (0, width - seg)))


def ring_all_reduce_segmented(x, axis: str = "rank", op: str = "sum",
                              seg_elems: Optional[int] = None,
                              interpret: bool = False):
    """Flat per-member [N] → [N] allreduced, one ring pass per segment
    of at most `seg_elems` elements (default: P chunks of
    RING_CHUNK_BYTES)."""
    P = lax.axis_size(axis)
    if P == 1:
        return x
    N = x.shape[0]
    seg = _seg_len(N, seg_elems or P * RING_CHUNK_BYTES // x.dtype.itemsize)
    width = _round_up(seg, P * _tile(x.dtype))
    blocks = _segments(x, seg, width).reshape(-1, width // LANES, LANES)

    def one(_, blk):
        return None, ring_all_reduce_pallas(blk, axis, op=op,
                                            interpret=interpret,
                                            cid_rs=0, cid_ag=1)

    _, out = lax.scan(one, None, blocks)
    return out.reshape(-1, width)[:, :seg].reshape(-1)[:N]


def ring_all_gather_segmented(x, axis: str = "rank",
                              seg_elems: Optional[int] = None,
                              interpret: bool = False):
    """Flat per-member [n] → [P * n] (rank-major), one ring pass per
    segment; blocks are re-interleaved so the layout matches one
    whole-payload all-gather."""
    P = lax.axis_size(axis)
    if P == 1:
        return x
    n = x.shape[0]
    seg = _seg_len(n, seg_elems or RING_CHUNK_BYTES // x.dtype.itemsize)
    width = _round_up(seg, _tile(x.dtype))
    blocks = _segments(x, seg, width).reshape(-1, width // LANES, LANES)

    def one(_, blk):
        return None, ring_all_gather_pallas(blk, axis, interpret=interpret,
                                            collective_id=0)

    _, out = lax.scan(one, None, blocks)  # [nseg, P, rows, 128]
    out = out.reshape(out.shape[0], P, width)[:, :, :seg]
    return jnp.swapaxes(out, 0, 1).reshape(P, -1)[:, :n].reshape(-1)


def ring_reduce_scatter_segmented(x, axis: str = "rank", op: str = "sum",
                                  seg_elems: Optional[int] = None,
                                  interpret: bool = False):
    """Flat per-member [P * n] (rank-major) → member's reduced [n], one
    ring pass per segment of the per-rank chunk dimension."""
    P = lax.axis_size(axis)
    if P == 1:
        return x
    n = x.shape[0] // P
    seg = _seg_len(n, seg_elems or RING_CHUNK_BYTES // x.dtype.itemsize)
    width = _round_up(seg, _tile(x.dtype))
    nseg = -(-n // seg)
    chunks = jnp.pad(x.reshape(P, n), ((0, 0), (0, nseg * seg - n)))
    chunks = jnp.pad(chunks.reshape(P, nseg, seg),
                     ((0, 0), (0, 0), (0, width - seg)))
    blocks = jnp.swapaxes(chunks.reshape(P, nseg, width // LANES, LANES),
                          0, 1)

    def one(_, blk):
        return None, ring_reduce_scatter_pallas(blk, axis, op=op,
                                                interpret=interpret,
                                                collective_id=0)

    _, out = lax.scan(one, None, blocks)  # [nseg, rows, 128]
    return out.reshape(nseg, width)[:, :seg].reshape(-1)[:n]
