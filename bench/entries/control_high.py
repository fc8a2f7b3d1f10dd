"""Entry ``control_high``: the lower-precision control, in the program's place.

The plain reference (``reference.py``) computed with three bfloat16 passes
per contraction (``precision="high"``), the precision just below the float32
at ``Precision.HIGHEST`` that the configuration states, solving the cell's
grid unscreened. The comparison has to read it as not correct.
``calibrate.py`` drives it through ``run.py --entry control_high``; the
benchmark's own runs never do.
"""

from __future__ import annotations

import jax
import numpy as np

import reference
from check import path_outputs


class Entry:
    def __init__(self, X, y, lambdas, kwargs: dict):
        self.X, self.y = X, y
        self.lambdas = np.asarray(lambdas, np.float32)
        self.tol, self.max_iters = kwargs["tol"], kwargs["max_iters"]

    def _solve(self, tol):
        return jax.block_until_ready(reference.solve_path(
            self.X, self.y, self.lambdas, tol, max_iters=self.max_iters,
            precision="high"))

    def warm(self):
        """The same program with a tolerance that stops each solve early
        (``tol`` is an argument of the compiled program)."""
        self._solve(1.0)

    def call(self):
        return self._solve(self.tol)

    def summary(self, r) -> dict:
        """The keys of ``path.Entry.summary``: every feature is kept."""
        m, n = (int(d) for d in self.X.shape)
        return dict(
            outputs=path_outputs(r.w, r.b, r.obj, r.gap,
                                 np.ones(np.shape(r.w), bool)),
            iters=np.asarray(r.iters, np.int64),
            kept=np.full(len(self.lambdas), m, np.int64),
            features=m,
            samples=n,
            healthy=True,
            converged=bool(np.all(np.asarray(r.converged))),
        )
