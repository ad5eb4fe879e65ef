"""The chip's published peaks, keyed by JAX's ``device_kind``.

The table and its source are in peaks.json beside this file.  A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(kind: str) -> dict:
    """The peaks of one chip of `kind`."""
    with open(_PATH) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {_PATH}; "
                       f"known: {sorted(table)}")
    return table[kind]
