"""Share of feature rows the solver had to carry, weighted by iterations:
sum_t iters_t * kept_t / sum_t iters_t * m, from the path's own counts."""

import numpy as np


def read(run):
    if not run.paths:
        return None
    p = run.paths[-1]
    iters = p["iters"].astype(np.float64)
    if iters.sum() == 0:
        return None
    return 100.0 * float(np.sum(iters * p["kept"]) / (iters.sum()
                                                      * p["features"]))
