"""busbw (GB/s): bus bytes of every allreduce completed in the window
(nccl-tests accounting, accounting.py) over the window's length."""
from accounting import bus_bytes


def read(run):
    per_unit = sum(bus_bytes(op, run.nranks, nbytes)
                   for op, nbytes in run.calls)
    return per_unit * run.units / run.window_s / 1e9
