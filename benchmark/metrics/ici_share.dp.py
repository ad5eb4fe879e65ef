"""ici_share.dp (%): bus bytes per chip of the calls in the traced
window (nccl-tests accounting, so the same work reads the same whatever
lowering serves it) over the device time of the collective programs,
as a share of the chip's inter-chip interconnect peak (peaks.json)."""
from accounting import bus_bytes
from peaks import peaks


def read(run):
    t = run.trace
    if t is None or not t.program_s:
        return None
    per_unit = sum(bus_bytes(op, run.nranks, nbytes)
                   for op, nbytes in run.calls)
    rate = per_unit * run.units / t.program_s
    return 100.0 * rate / peaks(run.device_kind)["ici_bytes_per_s"]
