"""alltoallv: per-peer row counts held on the device (the driver's
``ACCL.alltoallv``).

Rank i sends ``counts[i][j]`` rows of `row` elements to rank j, packed in
rank order in its send buffer; rank j receives every rank's rows packed
in source order and the counts it got (``counts[:, j]``).  What the
receive buffer holds past those rows is unspecified and not compared.  The reference builds each rank's expected receive
from every rank's send buffer and counts with numpy alone.
"""
from __future__ import annotations

import numpy as np

RING_LANE = False


def call(accl, send, recv, sendcounts, recvcounts, row: int):
    return accl.alltoallv(send, recv, sendcounts, recvcounts, row=row,
                          from_fpga=True, to_fpga=True)


def reference(sends: list, counts: list, row: int, recv_len: int) -> list:
    """Each rank's (expected received rows, flat, and expected receive
    counts): `sends` every rank's send buffer (flat), `counts` every
    rank's send counts, `recv_len` the receive buffer's elements."""
    P = len(sends)
    m = np.stack([np.asarray(c, np.int64) for c in counts])  # m[i, j]
    out = []
    for j in range(P):
        parts = []
        for i in range(P):
            off = int(m[i, :j].sum())
            rows = np.asarray(sends[i]).reshape(-1, row)
            parts.append(rows[off:off + int(m[i, j])])
        got = np.concatenate(parts).reshape(-1)
        if got.size > recv_len:
            raise ValueError(f"rank {j} receives {got.size} elements, "
                             f"more than its {recv_len}")
        out.append((got, m[:, j].astype(np.int32)))
    return out


def max_abs_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| in float64 over `want`'s elements, which
    `got` begins with (any NaN reads infinite); 0 without converting
    where the bytes are equal."""
    want = np.asarray(want).reshape(-1)
    got = np.asarray(got).reshape(-1)[:want.size]
    if got.dtype == want.dtype and np.array_equal(
            got.view(np.uint8), want.view(np.uint8)):
        return 0.0
    d = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    if d.size == 0:
        return 0.0
    return float(np.inf) if np.isnan(d).any() else float(d.max())
