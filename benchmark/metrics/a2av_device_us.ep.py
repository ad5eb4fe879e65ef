"""a2av_device_us.ep (us): device time of the alltoallv exchange per
call: the summed device duration of the ``ragged-all-to-all`` ops in the
traced window (profiler trace, per chip) over the window's alltoallv
calls.  None where the trace holds no such op (a platform that lowers
the exchange otherwise, or a program without alltoallv)."""


def exchange_s(trace) -> float:
    """Seconds of ``ragged-all-to-all`` ops per chip in `trace`: an op
    named in HLO form by its opcode, otherwise by its name."""
    total = 0.0
    for name, s in trace.ops:
        if " = " in name:
            hit = name.endswith(" ragged-all-to-all")
        else:
            hit = "ragged" in name and "all" in name
        if hit:
            total += s
    return total


def read(run):
    t = run.trace
    calls = sum(1 for op, _ in run.calls if op == "alltoallv") * run.units
    if t is None or not calls:
        return None
    s = exchange_s(t)
    return s / calls * 1e6 if s > 0 else None
