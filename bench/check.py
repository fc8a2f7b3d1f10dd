"""The comparison that decides ``correct``.

What a path of the timed entry returned is held against the plain reference
(``reference.py``) solved on the same data and grid. The numbers, each the
worst over the paths and steps compared:

* ``discarded_nonzero``: features a step screened out that are nonzero in
  the reference solution at that step (screening). The rule is safe, so the
  limit is 0.
* ``obj_excess``: how far the path's objective lies above the reference
  optimum, relative, both evaluated here from the weights in float64
  (solver).
* ``obj_report``: how far the objective the path reported lies from the one
  evaluated here in float64 from its own weights, relative (the precision
  of the fused sweeps that compute the margins and the loss inside the
  solve).
* ``gap_rel``: the duality gap the path certified, relative to the
  reference optimum (the certificate at the cell's ``tol``).

The workload's file names the numbers compared and their limits; the rest
are printed beside them. ``PERF.md`` gives the readings each limit was set
from.
"""

from __future__ import annotations

import numpy as np

import reference


def path_outputs(w, b, obj, gap, keep) -> dict:
    """The answers of one path that the comparison reads, as host arrays."""
    return dict(w=np.asarray(w, np.float32), b=np.asarray(b, np.float32),
                obj=np.asarray(obj, np.float64),
                gap=np.asarray(gap, np.float64),
                keep=np.asarray(keep, bool))


def readings(X, y, lambdas, paths, ref) -> dict:
    """Every number of the module docstring for ``paths`` (a list of
    :func:`path_outputs`) against ``ref`` (a ``reference.RefPath``). The
    objectives are evaluated in float64 on the host
    (``reference.objectives64``), once for each distinct path."""
    import jax.numpy as jnp

    lam = np.asarray(lambdas, np.float32)
    w_ref = np.asarray(ref.w)
    ws = [w_ref] + [p["w"] for p in paths]
    idx = np.flatnonzero(np.any(np.stack(ws) != 0.0, axis=(0, 1)))
    rows = idx, np.asarray(jnp.take(X, jnp.asarray(idx), axis=0), np.float64)
    p_ref = reference.objectives64(X, y, w_ref, np.asarray(ref.b), lam, rows)
    out = dict(discarded_nonzero=0, obj_excess=-np.inf, obj_report=0.0,
               gap_rel=0.0)
    evaluated = {}
    for p in paths:
        key = (p["w"].tobytes(), p["b"].tobytes())
        if key not in evaluated:
            evaluated[key] = reference.objectives64(X, y, p["w"], p["b"],
                                                    lam, rows)
        p_out = evaluated[key]
        out["discarded_nonzero"] += int(np.count_nonzero(
            (~p["keep"]) & (w_ref != 0.0)))
        out["obj_excess"] = max(out["obj_excess"],
                                float(np.max((p_out - p_ref) / p_ref)))
        out["obj_report"] = max(out["obj_report"], float(np.max(
            np.abs(p["obj"] - p_out) / p_out)))
        out["gap_rel"] = max(out["gap_rel"],
                             float(np.max(p["gap"] / p_ref)))
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: every limited number at or under its limit.
    A number that is not finite fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = values.get(name)
        passed = v is not None and bool(np.isfinite(v)) and v <= limit
        ok &= passed
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
