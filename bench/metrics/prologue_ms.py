"""Device milliseconds per path of the ops under ``svm_path/prologue``: the
per-call work on X before the first step (the Lipschitz estimate, the
sweeps' padded operand, the screening rules' fixed reductions)."""

import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run.trace, "svm_path/prologue")
