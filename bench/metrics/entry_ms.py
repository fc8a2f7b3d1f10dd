"""Host milliseconds of the entry around its device program, last traced path:
``1e3 x`` the ``last`` of the program's histogram ``path.entry_s`` (the
``svm_path`` wall less its ``svm_path.dispatch`` wall), read in-process from
``repro.obs.metrics``. Where paths ran and the histogram is not there, the
program has none: ``LookupError``."""


def read(run):
    from repro.obs import metrics

    h = metrics.snapshot().get("path.entry_s")
    if h is None and run is not None and run.paths:
        raise LookupError("paths ran, and the program has no histogram "
                          "path.entry_s")
    if h is None or h["last"] is None:
        return None
    return 1e3 * h["last"]
