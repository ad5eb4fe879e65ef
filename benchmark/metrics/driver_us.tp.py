"""driver_us.tp (us): median per call of the driver's own span, from
the user call's entry to its hand-off to the request queue
(ACCL_TRACE spans, submit -> queue)."""
import statistics


def read(run):
    xs = [s.t_queue - s.t_submit for s in run.spans or ()
          if s.t_submit is not None and s.t_queue is not None]
    return statistics.median(xs) / 1e3 if xs else None
