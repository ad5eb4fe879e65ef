"""collective_device_us.tp (us): device time of the collective programs
per call: the summed duration of the program executions on the device
in the traced window (profiler trace, per chip) over the calls issued
in it.  The benchmark runs no other program in the window."""


def read(run):
    t = run.trace
    if t is None or not t.programs:
        return None
    return t.program_s / run.units / len(run.calls) * 1e6
