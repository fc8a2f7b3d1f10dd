"""Share of the certification's feasibility rounds that ran, last traced path:
``100 x`` the ``last`` of the program's histogram
``path.certify_rounds_share`` (the path's ``feas_rounds``, the full sweeps
of X its certificates ran, over the cap of ``T x (n_feas_iters + 1)``), read
in-process from ``repro.obs.metrics``. Where paths ran and the histogram is
not there, the program has none: ``LookupError``."""


def read(run):
    from repro.obs import metrics

    h = metrics.snapshot().get("path.certify_rounds_share")
    if h is None and run is not None and run.paths:
        raise LookupError("paths ran, and the program has no histogram "
                          "path.certify_rounds_share")
    if h is None or h["last"] is None:
        return None
    return 100.0 * h["last"]
