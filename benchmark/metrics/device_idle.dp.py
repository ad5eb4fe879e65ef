"""device_idle.dp (%): share of the traced window in which no operation
ran on a device (profiler trace), averaged over the chips."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
