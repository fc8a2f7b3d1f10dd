"""Share of the certification's feasibility rounds whose rescale was
binding, last traced path: ``100 x`` the ``last`` of the program's histogram
``path.certify_binding_share`` (the path's ``feas_binding`` counts over
``T x (n_feas_iters + 1)`` rounds), read in-process from
``repro.obs.metrics``."""


def read(run):
    from repro.obs import metrics

    h = metrics.snapshot().get("path.certify_binding_share")
    if h is None or h["last"] is None:
        return None
    return 100.0 * h["last"]
