"""engine_finish_us.tp (us): median per call of result rebinding and
completion, the flight record, the metrics registry and waking the
waiter (ACCL_TRACE spans, launched -> complete; the stage's profiler
annotation is ``accl.finish``).  None where no span carries both
stamps, as on a program whose spans have no launched stamp."""
import statistics


def read(run):
    xs = []
    for s in run.spans or ():
        t0 = getattr(s, "t_launched", None)
        t1 = getattr(s, "t_complete", None)
        if t0 is not None and t1 is not None:
            xs.append(t1 - t0)
    return statistics.median(xs) / 1e3 if xs else None
