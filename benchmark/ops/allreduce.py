"""allreduce: every rank ends with the reduction of all ranks' inputs.

A collective's module under ``ops/`` is found by the ``op`` of a
configuration's unit, and gives the traffic its three op-specific parts:

``buffers(count, nranks)``
    the send and receive buffer's elements on each rank;
``call(accl, src, dst, count, function, **kw)``
    one driver call on device-resident operands;
``reference(inputs, function)``
    each rank's (expected result, magnitude) in float64 from every
    rank's input, with numpy alone; the magnitude is what the error of
    a served result is measured against (see reference.rel_err);
``RING_LANE``
    whether the engine may serve the op on its ring lane.

A reduce function the module does not know is an error, never a SUM.
"""
from __future__ import annotations

import numpy as np

RING_LANE = True


def buffers(count: int, nranks: int) -> tuple:
    return count, count


def call(accl, src, dst, count: int, function: str, **kw):
    from accl_tpu.constants import ReduceFunction

    return accl.allreduce(src, dst, count, ReduceFunction[function],
                          from_fpga=True, to_fpga=True, **kw)


def _sum(xs):
    # a floating-point sum's forward error is bounded by about (P - 1) u
    # times the sum of the magnitudes, whatever the order of adding
    return np.sum(xs, axis=0), np.sum(np.abs(xs), axis=0)


def _max(xs):
    return np.max(xs, axis=0), np.max(np.abs(xs), axis=0)


FUNCTIONS = {"SUM": _sum, "MAX": _max}


def reference(inputs: list, function: str) -> list:
    """The same (result, magnitude) for every rank."""
    if function not in FUNCTIONS:
        raise ValueError(f"no allreduce reference for {function!r}; "
                         f"known: {sorted(FUNCTIONS)}")
    xs = np.stack([np.asarray(x, np.float64) for x in inputs])
    pair = FUNCTIONS[function](xs)
    return [pair] * len(inputs)
