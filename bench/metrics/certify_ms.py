"""Device milliseconds per path of the ops under ``svm_path/certify``, its
feasibility rounds (``svm_path/certify/feasibility``) included."""

import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run.trace, "svm_path/certify")
