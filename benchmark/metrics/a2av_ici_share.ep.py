"""a2av_ici_share.ep (%): off-chip bytes per chip of the window's
alltoallv calls (the layer's device tally, reported by the generator as
each call's mean off-chip payload; busbw factor 1) over the exchange's
device time (``ragged-all-to-all`` ops, see a2av_device_us.ep), as a
share of the chip's inter-chip interconnect peak (peaks.json)."""
import os

import harness
from peaks import peaks

_dev = harness.load_module(os.path.dirname(os.path.abspath(__file__)), "",
                           "a2av_device_us.ep")


def read(run):
    t = run.trace
    if t is None:
        return None
    s = _dev.exchange_s(t)
    per_unit = sum(nbytes for op, nbytes in run.calls if op == "alltoallv")
    if s <= 0 or not per_unit:
        return None
    rate = per_unit * run.units / s
    return 100.0 * rate / peaks(run.device_kind)["ici_bytes_per_s"]
