"""What a collective moves, by the algorithm (nccl-tests accounting).

Bus bandwidth counts the bytes each rank must put on the wire under an
optimal algorithm, so it reads the same work whatever lowering serves
the call: an allreduce of S bytes per rank over P ranks moves
S * 2(P-1)/P.  Copied from the program's ``busbw_factor``
(accl_tpu/observability/metrics.py) so that no later change to the
program can change the yardstick.
"""
from __future__ import annotations

#: collectives whose per-rank payload is P blocks of `count` elements
XP_COLLECTIVES = ("allgather", "reduce_scatter", "alltoall")


def busbw_factor(coll: str, p: int) -> float:
    """Bus bytes per payload byte of `coll` over `p` ranks."""
    if p <= 1:
        return 1.0
    if coll == "allreduce":
        return 2.0 * (p - 1) / p
    if coll in XP_COLLECTIVES:
        return (p - 1) / p
    return 1.0


def bus_bytes(coll: str, p: int, payload_bytes: int) -> float:
    """Bus bytes of one call whose per-rank payload is `payload_bytes`."""
    return payload_bytes * busbw_factor(coll, p)
