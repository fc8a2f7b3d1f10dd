"""Host milliseconds of the entry around its device program, last traced
path: ``1e3 x`` the ``last`` of the program's histogram ``path.entry_s``
(the ``svm_path`` wall less its ``svm_path.dispatch`` wall), read in-process
from ``repro.obs.metrics``."""


def read(run):
    from repro.obs import metrics

    h = metrics.snapshot().get("path.entry_s")
    if h is None or h["last"] is None:
        return None
    return 1e3 * h["last"]
