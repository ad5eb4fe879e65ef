"""engine_marshal_us.tp (us): median per call of the dispatch's own
work, plan lookup, operand assembly and link and lane accounting
(ACCL_TRACE spans, dispatch -> launch; the stage's profiler annotation
is ``accl.marshal``).  None where no span carries both stamps, as on a
program whose spans have no launch stamp."""
import statistics


def read(run):
    xs = []
    for s in run.spans or ():
        t0, t1 = getattr(s, "t_dispatch", None), getattr(s, "t_launch", None)
        if t0 is not None and t1 is not None:
            xs.append(t1 - t0)
    return statistics.median(xs) / 1e3 if xs else None
