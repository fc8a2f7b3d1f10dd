"""Share of the solve's device time that the chip's roofline needs for the
work the algorithm must do: one read of the kept rows per iteration,
sum_t iters_t * kept_t * n * 4 bytes, and 4 * kept_t * n FLOPs per
iteration (a margin and a gradient multiply-add per entry). The least time
is the larger of bytes over the peak bandwidth and FLOPs over the peak
rate; the share is that over ``solve_ms``. Whatever implements the solve,
the same work is counted, so a one-pass kernel can reach 100%."""

import numpy as np


def work(iters, kept, samples):
    """(bytes, flops) one path's solves need."""
    rows = float(np.sum(np.asarray(iters, np.float64) * kept))
    return 4.0 * rows * samples, 4.0 * rows * samples


def read(run):
    if run.trace is None or run.trace["paths"] == 0 or not run.paths:
        return None
    solve_s = run.trace["scopes"].get("svm_path/solve")
    if not solve_s:
        return None
    p = run.paths[-1]
    nbytes, flops = work(p["iters"], p["kept"], p["samples"])
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / (solve_s / run.trace["paths"])
