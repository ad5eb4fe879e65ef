"""token_p95_ms (ms): the 95th percentile of the time of every unit
(token) in the window, linearly interpolated (numpy's default)."""
import numpy as np


def read(run):
    return float(np.percentile(run.unit_s, 95)) * 1e3
