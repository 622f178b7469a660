"""95th percentile of the host wall time of every event in the window."""
import numpy as np


def read(run):
    if not run.units:
        return None
    return float(np.percentile([u.wall_s for u in run.units], 95) * 1e3)
