"""Functional SPMD collectives — the XLA lowering layer.

These are meant to be called *inside* `shard_map`/`pjit`-traced functions
over a mesh axis.  Each maps one reference collective onto its XLA HLO
equivalent, which the TPU compiler schedules over ICI links (BASELINE
north star: HLO collectives replace the CCLO offload engine):

| reference firmware schedule           | here                          |
|---------------------------------------|-------------------------------|
| segmented ring allreduce (fw :1888)   | lax.psum (+ ring_all_reduce)  |
| ring allgather (fw :1299)             | lax.all_gather                |
| ring reduce_scatter (fw :1748)        | lax.psum_scatter              |
| fused flat-tree alltoall (fw :2123)   | lax.all_to_all                |
| tree/flat bcast (fw :798)             | all_gather + index            |
| daisy-chain/tree reduce (fw :1509)    | psum/pmax (root keeps)        |
| tagged send/recv (fw :575/:655)       | lax.ppermute pairs            |

The explicit `ring_*` variants express the reference's ring schedules
directly with `ppermute` steps — useful when manual overlap beats XLA's
built-in lowering, and as the scheduling skeleton the Pallas kernels
(accl_tpu.ops.ring) implement with remote DMA.
"""
from __future__ import annotations


import jax.numpy as jnp
from jax import lax



# ---------------------------------------------------------------------------
# direct XLA lowerings
# ---------------------------------------------------------------------------
def all_reduce(x, axis: str = "rank", op: str = "sum"):
    """All-reduce over a mesh axis (fw allreduce :1855-2075)."""
    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    raise ValueError(f"unknown reduce op {op!r}")


def reduce(x, root: int, axis: str = "rank", op: str = "sum"):
    """Rooted reduce: every member computes the reduction, the caller
    keeps the root's copy (fw reduce :1509-1744).  On TPU the replicated
    compute is free relative to the collective itself."""
    return all_reduce(x, axis, op)


def all_gather(x, axis: str = "rank", tiled: bool = True, gather_axis: int = 0):
    """All-gather over a mesh axis (fw allgather :1299-1505)."""
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str = "rank", scatter_axis: int = 0):
    """Reduce-scatter over a mesh axis (fw reduce_scatter :1748-1852)."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                            tiled=True)


def all_to_all(x, axis: str = "rank", split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = True):
    """All-to-all personalized exchange (fw all_to_all :2123-2218)."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def broadcast(x, root: int, axis: str = "rank"):
    """Broadcast the root's value to every member (fw bcast :798-990)."""
    return lax.all_gather(x, axis)[root]


def scatter(x, root: int, axis: str = "rank"):
    """Scatter the root's rank-major blocks: member i receives block i
    (fw scatter :994-1125).  `x` must have leading dim = axis size."""
    row = lax.all_gather(x, axis)[root]
    idx = lax.axis_index(axis)
    return lax.dynamic_index_in_dim(row, idx, axis=0, keepdims=False)


def gather(x, root: int, axis: str = "rank"):
    """Gather members' blocks; caller keeps the root's copy
    (fw gather :1130-1296)."""
    return lax.all_gather(x, axis)


def ppermute(x, perm, axis: str = "rank"):
    """Point-to-point permutation — the tagged send/recv equivalent."""
    return lax.ppermute(x, axis, perm)


def send_recv(x, src: int, dst: int, axis: str = "rank"):
    """Single-pair transfer: `dst` receives `src`'s value, everyone else
    receives zeros (fw send/recv :575-712)."""
    return lax.ppermute(x, axis, [(src, dst)])


def barrier(axis: str = "rank"):
    """Synchronization via a trivial psum (fw barrier :2077-2120 —
    gather+scatter of empty messages; on TPU any collective is a sync)."""
    return lax.psum(jnp.ones((), jnp.int32), axis)


# ---------------------------------------------------------------------------
# explicit ring schedules (the reference's firmware schedules, expressed
# with ppermute steps; XLA overlaps consecutive steps across ICI)
# ---------------------------------------------------------------------------
def ring_reduce_scatter(x, axis: str = "rank"):
    """Ring reduce-scatter (fw :1782-1850): P-1 steps, each sending the
    running partial one hop forward and folding the arriving chunk.
    `x`: [P * n, ...] per member → returns member's reduced chunk [n, ...].
    """
    size = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    n = x.shape[0] // size
    chunks = x.reshape((size, n) + x.shape[1:])
    fwd = [(i, (i + 1) % size) for i in range(size)]

    def step(s, carry):
        # the chunk sent this step is (idx - 1 - s) mod size; only the
        # arriving chunk index below is needed to fold the reduction
        partial = carry
        moved = lax.ppermute(partial, axis, fwd)
        recv_c = (idx - 2 - s) % size
        return moved + jnp.take(chunks, recv_c, axis=0)

    first = jnp.take(chunks, (idx - 1) % size, axis=0)
    # s=0 already "holds" chunk (idx-1); fold P-1 arrivals
    out = lax.fori_loop(0, size - 1, step, first)
    return out


def ring_all_gather(x, axis: str = "rank"):
    """Ring all-gather (fw :1404-1502): P-1 steps, forwarding the newest
    block each step.  `x`: [n, ...] → [P * n, ...] in rank-major order."""
    size = lax.axis_size(axis)
    idx = lax.axis_index(axis)

    def step(s, carry):
        out, cur = carry
        cur = lax.ppermute(cur, axis, [(i, (i + 1) % size) for i in range(size)])
        origin = (idx - 1 - s) % size
        out = lax.dynamic_update_slice_in_dim(out, cur[None], origin * 1,
                                              axis=0)
        return out, cur

    out0 = jnp.zeros((size,) + x.shape, x.dtype)
    out0 = lax.dynamic_update_slice_in_dim(out0, x[None], idx * 1, axis=0)
    out, _ = lax.fori_loop(0, size - 1, step, (out0, x))
    return out.reshape((size * x.shape[0],) + x.shape[1:])


def ring_all_reduce(x, axis: str = "rank"):
    """Segmented ring allreduce = ring reduce-scatter + ring all-gather
    fused (fw :1888-2071).  `x`: [P * n, ...] with P | x.shape[0]."""
    chunk = ring_reduce_scatter(x, axis)
    return ring_all_gather(chunk, axis)


def hierarchical_all_reduce(x, ici_axis: str, dcn_axis: str):
    """Two-level allreduce for multi-slice meshes: reduce-scatter inside
    the slice (ICI), all-reduce the shards across slices (DCN), then
    all-gather back inside the slice.  Crosses DCN with 1/|ici| of the
    bytes a flat psum over both axes would — the same
    bandwidth-hierarchy trick as the reference's ring schedules over its
    100G POE links (fw allreduce :1888-2071), applied to the ICI/DCN
    hierarchy of a multi-slice mesh (`make_hybrid_mesh`).

    `x`'s leading dim must be divisible by the ici axis size.
    """
    shard = lax.psum_scatter(x, ici_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, dcn_axis)
    return lax.all_gather(shard, ici_axis, axis=0, tiled=True)
