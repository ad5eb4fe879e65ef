"""host_us.dp (us): median per call, over every rank, of the host path
from the user call's entry to the program's dispatch, gang assembly
included (ACCL_TRACE spans, submit -> dispatch)."""
import statistics


def read(run):
    xs = [s.t_dispatch - s.t_submit for s in run.spans or ()
          if s.t_submit is not None and s.t_dispatch is not None]
    return statistics.median(xs) / 1e3 if xs else None
