"""Bytes that the grouped expert matmuls of one MoE layer on one chip
must move through HBM, from the shapes (the yardstick of
``experts_hbm_share.scmoe``): every held expert's three projections
read once, and the received rows in and out of each projection, bf16
throughout.  Padding rows, the VMEM traffic and the router are not
counted: this is the least the three kernels can move."""
from __future__ import annotations

BF16 = 2


def weight_bytes(hidden: int, width: int, held: int) -> float:
    """The held experts' W1, W3 [hidden, width] and W2 [width, hidden]."""
    return 3.0 * held * hidden * width * BF16


def row_bytes(hidden: int, width: int, rows: float) -> float:
    """`rows` received rows: read by W1 and W3, their two outputs
    written, the SwiGLU product read by W2, and W2's output written."""
    return rows * (2 * hidden + 2 * width + width + hidden) * BF16


def layer_bytes(hidden: int, width: int, held: int, rows: float) -> float:
    """One layer's three grouped matmuls on a chip holding `held`
    experts that received `rows` rows."""
    return weight_bytes(hidden, width, held) + row_bytes(hidden, width, rows)
