"""Device milliseconds per path of the ops under ``svm_path/screen``."""


def read(run):
    if run.trace is None or run.trace["paths"] == 0:
        return None
    s = run.trace["scopes"].get("svm_path/screen")
    return None if s is None else 1e3 * s / run.trace["paths"]
