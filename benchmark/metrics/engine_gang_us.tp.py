"""engine_gang_us.tp (us): median per call of the gang scheduler, gang
join and the hand-off to the dispatching thread (ACCL_TRACE spans,
queue -> dispatch; the stage's profiler annotation is ``accl.gang``).
None where no span carries both stamps."""
import statistics


def read(run):
    xs = []
    for s in run.spans or ():
        t0, t1 = getattr(s, "t_queue", None), getattr(s, "t_dispatch", None)
        if t0 is not None and t1 is not None:
            xs.append(t1 - t0)
    return statistics.median(xs) / 1e3 if xs else None
