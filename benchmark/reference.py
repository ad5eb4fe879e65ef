"""How a served result is judged against the plain reference, and the
lower-precision carriers that the control computes the reference in.

Each collective's reference (ops/<op>.py) gives, for every rank, the
expected result in float64 from the inputs the benchmark made, and the
magnitude it is measured against; it imports nothing of the program.
A served output is judged by

    rel_err = max_i |got_i - ref_i| / mag_i

For a sum, mag is the sum of the magnitudes of what was added, element
by element: the forward error bound of a floating-point sum, so any
order of adding P values in a format with unit roundoff u keeps
rel_err under about (P - 1) u, whatever the values' signs and sizes.
An output that is bitwise the exact result reads 0.

The control is the reference computed one precision lower than the
configuration states, where the program has no such path of its own:
a traffic file names the carrier in ``control.lower_reference``, a key
of :data:`CARRIERS`.  :func:`int8_roundtrip` is the block-scaled int8
carrier (one float32 scale per 256 elements, amax / 127) below
bfloat16.
"""
from __future__ import annotations

import numpy as np

INT8_BLOCK = 256


def rel_err(got, ref: np.ndarray, mag: np.ndarray) -> float:
    """Largest error of `got` against `ref`, relative to `mag` (0/0 = 0;
    any error where nothing was added is infinite)."""
    diff = np.abs(np.asarray(got, np.float64) - ref)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(diff == 0, 0.0, diff / mag)
    return float(r.max()) if r.size else 0.0


def int8_roundtrip(x) -> np.ndarray:
    """`x` carried as block-scaled int8 and back, in float64."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    pad = -n % INT8_BLOCK
    blocks = np.concatenate([x, np.zeros(pad)]).reshape(-1, INT8_BLOCK)
    scale = np.abs(blocks).max(axis=1, keepdims=True) / 127.0
    scale[scale == 0] = 1.0
    q = np.clip(np.rint(blocks / scale), -127, 127)
    return (q * scale).reshape(-1)[:n]


#: lower-precision carriers, by the name a traffic file gives
CARRIERS = {"int8": int8_roundtrip}


def carrier(name: str):
    if name not in CARRIERS:
        raise ValueError(f"no carrier {name!r} for a lowered reference; "
                         f"known: {sorted(CARRIERS)}")
    return CARRIERS[name]
