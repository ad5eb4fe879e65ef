"""engine_launch_us.tp (us): median per call of the host's call of the
compiled program, which on the chip returns at enqueue (ACCL_TRACE
spans, launch -> launched; the stage's profiler annotation is
``accl.launch``).  None where no span carries both stamps, as on a
program whose spans have no launch stamps."""
import statistics


def read(run):
    xs = []
    for s in run.spans or ():
        t0, t1 = getattr(s, "t_launch", None), getattr(s, "t_launched", None)
        if t0 is not None and t1 is not None:
            xs.append(t1 - t0)
    return statistics.median(xs) / 1e3 if xs else None
