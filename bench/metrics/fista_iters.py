"""FISTA iterations per path, summed over the steps (the path's own count)."""

import numpy as np


def read(run):
    if not run.paths:
        return None
    return float(np.mean([p["iters"].sum() for p in run.paths]))
