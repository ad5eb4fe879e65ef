"""engine_us.tp (us): median per call of the gang scheduler and
dispatch, from the request queue to completion (ACCL_TRACE spans,
queue -> complete)."""
import statistics


def read(run):
    xs = [s.t_complete - s.t_queue for s in run.spans or ()
          if s.t_queue is not None and s.t_complete is not None]
    return statistics.median(xs) / 1e3 if xs else None
