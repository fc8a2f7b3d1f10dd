"""Entry ``path``: one certified lambda path through ``repro.core.svm_path``.

The workload's ``kwargs`` are passed to ``svm_path`` as they stand; the grid
is passed as ``lambdas``. X and y stay on the device across calls.
"""

from __future__ import annotations

import numpy as np

from check import path_outputs


class Entry:
    def __init__(self, X, y, lambdas, kwargs: dict):
        from repro.core import svm_path

        self._svm_path = svm_path
        self.X, self.y = X, y
        self.lambdas = np.asarray(lambdas, np.float64)
        self.kwargs = dict(kwargs)

    def warm(self):
        """Compile and load every program a call runs: the same call, with a
        tolerance that stops each solve after its first iterations (``tol``
        is an argument of the compiled program, not part of its key)."""
        self._svm_path(self.X, self.y, lambdas=self.lambdas,
                       **{**self.kwargs, "tol": 1.0})

    def call(self):
        """One path, returned to the host (``svm_path`` blocks on it)."""
        return self._svm_path(self.X, self.y, lambdas=self.lambdas,
                              **self.kwargs)

    def summary(self, r) -> dict:
        """What the harness keeps of a path: the answers the comparison
        reads, and the counts the metrics and ``failed`` read."""
        return dict(
            outputs=path_outputs(r.weights, r.biases, r.objectives,
                                 r.extras["gaps"], r.extras["keep_masks"]),
            iters=np.asarray(r.solver_iters, np.int64),
            kept=np.asarray(r.kept, np.int64),
            features=int(self.X.shape[0]),
            samples=int(self.X.shape[1]),
            healthy=not np.asarray(r.extras["health"]).any(),
            converged=bool(np.all(r.extras["converged"])),
        )
