"""Rule-agnostic regularization-path driver with pluggable screening.

Walks a decreasing grid ``lam_max = lam_0 > lam_1 > ... > lam_{T-1}``. At each
step the previous primal/dual pair parameterizes a
:class:`~repro.core.rules.base.ConvexRegion`; every configured
:class:`~repro.core.rules.base.ScreeningRule` then contributes a keep-mask on
its axis (feature rows and/or sample columns of ``X``), the reduced problem
is solved with a warm-started FISTA, and the solution is scattered back to
full coordinates. Rules that are not a-priori safe (``needs_verification``)
are checked at the solved point and violators re-admitted before the step is
accepted — so the accepted solution is exact regardless of screening.

Two execution modes, applied on *both* axes:

* ``reduce="gather"`` — physically gathers kept rows/columns (padded to a
  power-of-two bucket so jit re-traces at most O(log) times). Solver cost
  scales with ``kept_features x kept_samples`` — the multiplicative payoff of
  simultaneous reduction.
* ``reduce="mask"``   — static shapes; screened features are zeroed rows,
  screened samples are dropped from the loss via the solver's
  ``sample_mask`` (zeroing columns would *not* be equivalent: an all-zero
  column still contributes ``max(0, 1 - y_i b)^2`` to the loss).

Trust-region movement estimates for the sample rule come from observed path
movement: after each accepted step the driver records
``||w_k - w_{k-1}||_2`` and ``|b_k - b_{k-1}|`` and predicts the next step's
movement as ``shrink_factor`` times that (first-order continuation on a
geometric grid). The first screened step has no history and keeps all
samples — correct anyway, since near ``lam_max`` nearly every sample is a
support vector.

Exactness: feature rules are safe given ``||theta1 - theta*|| <= delta``
(gap-certified, see dual.safe_theta_and_delta); sample rules are exact at
termination via the verification loop. Property tests cover both
(tests/test_screening.py, tests/test_rules.py).

Engines: this host-orchestrated driver (``engine="host"``) is one of two
path engines — ``core/path_scan.py`` runs the same feature-screened path as
a single jitted ``lax.scan`` program (``engine="scan"``), trading the
gather-mode FLOP reduction and the sample-verification loop for zero
per-step host round trips. Rule of thumb: gather mode shrinks FLOPs, scan
mode kills orchestration overhead. ``svm_path(engine=...)`` selects.

The Lipschitz constant is estimated once per path on the full ``X`` and
reused by every reduced solve — masking/gathering rows or columns never
increases ``sigma_max``, so the full-matrix estimate stays a valid step
bound (and saves the 30-iteration power sweep per solve, per verification
round). ``exact_lipschitz=True`` restores the per-solve estimate.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs.path_trace import build_path_trace
from repro.obs.trace import span

from .dual import (
    bias_at_lambda_max,
    lambda_max,
    safe_theta_and_delta,
    theta_at_lambda_max,
)
from .rules import (
    AXIS_FEATURES,
    AXIS_SAMPLES,
    ConvexRegion,
    FeatureVIRule,
    SampleVIRule,
    make_rules,
)
from .rules.base import dynamic_tau, solve_with_verification
from .screening import SAFE_TAU, anchor_stats
from .solver import (
    HEALTH_SCREEN_REFUSED,
    DynamicFistaResult,
    fista_solve,
    fista_solve_dynamic,
    lipschitz_estimate,
)


def _anchor_ok(theta, delta) -> bool:
    """Host-side certificate gate: screening regions may only be built from
    a finite anchor. A poisoned ``(theta, delta)`` (NaN'd solve, inf'd gap)
    must fail-safe to keep-all for the next step — host rule bounds compare
    ``bounds >= tau``, where a NaN silently discards."""
    return bool(np.isfinite(float(delta))
                and np.all(np.isfinite(np.asarray(theta))))


def _is_chunked(X) -> bool:
    """Duck-typed ``repro.sparse.FeatureChunked`` check (no import cycle)."""
    return hasattr(X, "stream") and hasattr(X, "gather_rows")


def _validate_grid(lambdas) -> np.ndarray:
    lambdas = np.asarray(lambdas, dtype=np.float64)
    if lambdas.size == 0:
        raise ValueError("empty lambda grid")
    if not np.all(np.isfinite(lambdas)) or np.any(lambdas <= 0):
        raise ValueError(f"lambda grid must be finite and positive: {lambdas}")
    if np.any(np.diff(lambdas) >= 0):
        raise ValueError(
            "lambda grid must be strictly decreasing (screening regions "
            f"certify theta*(lam2) only for lam2 < lam1): {lambdas}"
        )
    return lambdas

__all__ = ["PathResult", "PathDriver", "svm_path", "default_lambda_grid"]


@dataclass
class PathResult:
    lambdas: np.ndarray            # (T,)
    weights: np.ndarray            # (T, m)
    biases: np.ndarray             # (T,)
    objectives: np.ndarray         # (T,)
    kept: np.ndarray               # (T,) kept feature count fed to the solver
    active: np.ndarray             # (T,) nnz(w) in the solution
    solver_iters: np.ndarray       # (T,)
    wall_times: np.ndarray         # (T,) seconds per step (solve + screen)
    screen_times: np.ndarray       # (T,) seconds spent screening
    screened: bool = True
    kept_samples: np.ndarray = None  # (T,) samples fed to the solver
    verify_rounds: np.ndarray = None  # (T,) sample-verification re-solves
    rules: tuple = ()
    extras: dict = field(default_factory=dict)


def default_lambda_grid(lam_max_val: float, n_lambdas: int = 10, lam_min_ratio: float = 0.1) -> np.ndarray:
    return np.geomspace(lam_max_val, lam_max_val * lam_min_ratio, n_lambdas)


def _bucket(n: int) -> int:
    """Round up to the next power of two (min 8) to bound retracing."""
    b = 8
    while b < n:
        b *= 2
    return b


def _dynamic_telemetry(res: DynamicFistaResult) -> dict:
    """Host-side view of one dynamic solve's per-segment screening trace."""
    s = int(res.n_segments)
    out = {
        "segments": s,
        "kept_per_segment": [int(v) for v in np.asarray(res.kept_per_segment)[:s]],
        "gap_per_segment": [float(v) for v in np.asarray(res.gap_per_segment)[:s]],
    }
    if res.kept_samples_per_segment is not None:
        out["kept_samples_per_segment"] = [
            int(v) for v in np.asarray(res.kept_samples_per_segment)[:s]
        ]
    return out


class PathDriver:
    """Applies an arbitrary list of screening rules along the lambda path.

    ``rules`` accepts anything :func:`~repro.core.rules.base.make_rules`
    does: ``"feature_vi"``, ``"sample_vi"``, ``"composite"``, a list of
    names, or rule instances. An empty list solves the unscreened path.
    """

    def __init__(
        self,
        rules="feature_vi",
        *,
        reduce: str = "gather",
        tol: float = 1e-9,
        max_iters: int = 4000,
        shrink_factor: float = 1.5,
        max_verify_rounds: int = 3,
        dynamic: bool = False,
        screen_every: int = 50,
        exact_lipschitz: bool = False,
        use_pallas: Optional[bool] = None,
        L=None,
        chunk_skip: bool = True,
        guards: Optional[bool] = None,
    ):
        """``dynamic=True`` swaps every solve for the segmented
        ``solver.fista_solve_dynamic``: the step's sequential screen seeds a
        live feature mask that the solver keeps tightening every
        ``screen_every`` iterations from the gap-certified at-lambda region.
        Per-step, per-segment kept-counts/gaps land in
        ``PathResult.extras["dynamic"]``. Safe with any rule mix (the
        in-solver screen is a-priori safe on its own certificate).

        ``exact_lipschitz=True`` re-estimates L per reduced solve instead of
        reusing the full-X upper bound computed once per path (see module
        docstring); ``use_pallas`` routes the FISTA hot-loop sweeps through
        the fused Pallas kernels (None = env/backend policy).

        ``L`` (optional): a known upper bound on the Lipschitz constant of
        ``[X; 1^T]`` — skips the per-path power iteration entirely. The
        bound is a property of the matrix, not of how it is stored, so
        passing one value to several storage engines (dense / chunked /
        CSR) gives them floating-point-identical step sizes and keeps
        their trajectories comparable to solver tolerance (the streamed
        estimator reassociates its reductions, and near fp32 plateau ties
        even 1-ulp step-size differences move the stopping point).

        ``chunk_skip`` (chunked storage only): certify whole feature-row
        chunks dead from their cached stale-anchor bounds *before* the
        ``device_put`` and skip their transfers entirely (see
        ``sparse/screen_stream.ChunkScreenCache``). ``False`` runs the
        full-stream twin — identical screening decisions and path, every
        chunk transferred — the equivalence/bench baseline. No effect on
        in-core storage."""
        if reduce not in ("gather", "mask"):
            raise ValueError(
                f"host-driver reduce must be 'gather' or 'mask', got "
                f"{reduce!r} ('compact' is the scan engine's on-device "
                "gather — use svm_path(engine='scan', reduce='compact'))"
            )
        self.rules = make_rules(rules)
        self.reduce = reduce
        self.tol = float(tol)
        self.max_iters = int(max_iters)
        self.shrink_factor = float(shrink_factor)
        self.max_verify_rounds = int(max_verify_rounds)
        self.dynamic = bool(dynamic)
        self.screen_every = int(screen_every)
        self.exact_lipschitz = bool(exact_lipschitz)
        self.use_pallas = use_pallas
        if L is not None and exact_lipschitz:
            raise ValueError("pass either L= (a known bound) or "
                             "exact_lipschitz=True (per-solve estimates), "
                             "not both")
        self.L = L
        self.chunk_skip = bool(chunk_skip)
        # numerical health guards (core/solver.py): None resolves the
        # REPRO_SOLVER_GUARDS env default at each solve dispatch
        self.guards = guards
        # fault-injection seam (testing/faults.py): called as
        # ``injector(k, w_full, b_new) -> (w_full, b_new)`` on the accepted
        # solution of step k, BEFORE it is recorded, certified, and warm-
        # starts step k+1 — a poisoned return exercises the whole recovery
        # chain (refused certificate -> keep-all -> sanitized warm start).
        self._fault_injector = None

    # -- reduction helpers -------------------------------------------------

    def _feature_select(self, X_np, f_idx, m):
        """Bucket-padded gather of kept feature rows (zeroed padding)."""
        pad = min(_bucket(max(len(f_idx), 1)), m)
        sel = np.zeros((pad,), dtype=np.int64)
        sel[: len(f_idx)] = f_idx
        valid = np.arange(pad) < len(f_idx)
        return sel, valid

    def _solve(self, Xr, yr, lam, w0, b0, sample_mask, feature_mask=None,
               L=None, sample_screen_kw=None):
        if self.dynamic:
            return fista_solve_dynamic(
                Xr, yr, jnp.asarray(lam), w0=w0, b0=b0,
                max_iters=self.max_iters, tol=self.tol, L=L,
                sample_mask=sample_mask,
                feature_mask=feature_mask,
                screen_every=self.screen_every, tau=dynamic_tau(self.rules),
                use_pallas=self.use_pallas, guards=self.guards,
                **(sample_screen_kw or {}),
            )
        return fista_solve(
            Xr, yr, jnp.asarray(lam), w0=w0, b0=b0,
            max_iters=self.max_iters, tol=self.tol, L=L,
            sample_mask=sample_mask, use_pallas=self.use_pallas,
            guards=self.guards,
        )

    # -- main loop ---------------------------------------------------------

    def run(
        self,
        X: jax.Array,
        y: jax.Array,
        lambdas: Optional[Sequence[float]] = None,
        n_lambdas: int = 10,
        lam_min_ratio: float = 0.1,
    ) -> PathResult:
        """``X`` may be a dense ``(m, n)`` array or a
        ``repro.sparse.FeatureChunked`` container — the latter runs the
        out-of-core lane (:meth:`_run_chunked`): screening streams chunk by
        chunk and the solver sees only the gathered surviving rows."""
        if _is_chunked(X):
            return self._run_chunked(X, y, lambdas=lambdas,
                                     n_lambdas=n_lambdas,
                                     lam_min_ratio=lam_min_ratio)
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        m, n = X.shape
        X_np = np.asarray(X)
        y_np = np.asarray(y)

        feature_rules = [r for r in self.rules if r.axis == AXIS_FEATURES]
        sample_rules = [r for r in self.rules if r.axis == AXIS_SAMPLES]
        for rule in self.rules:
            rule.prepare(X, y)

        # one Lipschitz estimate serves every solve of the path (including
        # verification re-solves): sigma_max of a masked/gathered subproblem
        # never exceeds the full X's. Opt out via exact_lipschitz=True.
        if self.L is not None:
            L_path = jnp.asarray(self.L, X.dtype)
        else:
            L_path = None if self.exact_lipschitz else lipschitz_estimate(X)

        lam_max_val = float(lambda_max(X, y))
        if lambdas is None:
            lambdas = default_lambda_grid(lam_max_val, n_lambdas, lam_min_ratio)
        lambdas = _validate_grid(lambdas)
        T = len(lambdas)

        weights = np.zeros((T, m), dtype=np.float64)
        biases = np.zeros((T,), dtype=np.float64)
        objectives = np.zeros((T,), dtype=np.float64)
        kept = np.zeros((T,), dtype=np.int64)
        kept_s = np.zeros((T,), dtype=np.int64)
        vrounds = np.zeros((T,), dtype=np.int64)
        active = np.zeros((T,), dtype=np.int64)
        iters = np.zeros((T,), dtype=np.int64)
        wall = np.zeros((T,), dtype=np.float64)
        s_times = np.zeros((T,), dtype=np.float64)
        c_times = np.zeros((T,), dtype=np.float64)  # certification walls
        deltas_log = np.full((T,), np.nan, dtype=np.float64)
        health = np.zeros((T,), dtype=np.int64)  # guard telemetry per step
        sample_masks: dict[int, np.ndarray] = {}  # accepted per-step masks

        dyn_log: dict[int, dict] = {}  # per-step in-solver screening telemetry
        # per-step, per-feature-rule screen telemetry: kept count and bound
        # spread for every rule *individually* (the masks are intersected,
        # so per-rule keeps are not recoverable from the final mask). Feeds
        # extras["rule_telemetry"], the bench rules sweep, and AutoRule's
        # cost model. Entry 0 is the unscreened closed-form/cold step.
        rule_log: list[dict[str, dict]] = [{}]
        lam_prev = float(lambdas[0])
        w_host = np.zeros((m,), dtype=np.float64)
        if lambdas[0] >= lam_max_val * (1.0 - 1e-9):
            # step 0 at (or above) lam_max: closed form (w = 0, b = mean y)
            # is *exact*, so delta = 0 and theta is the true dual optimum
            b0 = float(bias_at_lambda_max(y))
            theta_prev = theta_at_lambda_max(y, jnp.asarray(lambdas[0]))
            delta_prev = jnp.asarray(0.0, X.dtype)
            biases[0] = b0
            xi0 = np.maximum(0.0, 1.0 - y_np * b0)
            objectives[0] = 0.5 * float(np.sum(xi0 * xi0))
            b_host = b0
        else:
            # custom grid starting below lambda_max: the closed form does NOT
            # hold (w*(lambdas[0]) != 0). Solve step 0 with FISTA — no anchor
            # exists yet, so it is unscreened — and certify theta via the gap
            # bound instead of assuming exactness.
            t0 = time.perf_counter()
            res0 = self._solve(
                X, y, float(lambdas[0]),
                jnp.zeros((m,), X.dtype), jnp.mean(y), None, L=L_path,
            )
            jax.block_until_ready(res0)  # stamp *finished* device work
            wall[0] = time.perf_counter() - t0
            w_host = np.asarray(res0.w, dtype=np.float64)
            b_host = float(res0.b)
            weights[0] = w_host
            biases[0] = b_host
            objectives[0] = float(res0.obj)
            kept[0] = m
            active[0] = int(np.sum(np.abs(w_host) > 1e-10))
            iters[0] = int(res0.n_iters)
            if isinstance(res0, DynamicFistaResult):
                dyn_log[0] = _dynamic_telemetry(res0)
            if res0.health is not None:
                health[0] |= int(res0.health)
            theta_prev, delta_prev = safe_theta_and_delta(
                X, y, jnp.asarray(w_host, X.dtype), jnp.asarray(b_host, X.dtype),
                jnp.asarray(float(lambdas[0])),
            )
        anchor_ok = _anchor_ok(theta_prev, delta_prev)
        deltas_log[0] = float(delta_prev)
        # trust-region movement state (inf until one step of history exists)
        dw_pred = float("inf")
        db_pred = float("inf")

        # dynamic *sample* re-screen: with dynamic=True, a sample rule, and
        # mask-mode reduction (static shapes — the in-solver mask indexes
        # global samples), the segmented solver also re-checks margins
        # in-loop, using the rule's slack model. Gather mode keeps the
        # driver-level (between-lambda) sample screen only.
        dyn_sample_rule = None
        if self.dynamic and self.reduce == "mask":
            dyn_sample_rule = next(
                (r for r in sample_rules if isinstance(r, SampleVIRule)), None)

        for k in range(1, T):
            lam = float(lambdas[k])
            with span("path.step", step=k, lam=lam) as step_span:
                t0 = time.perf_counter()

                with span("path.screen", step=k) as screen_span:
                    # -- screening: one region, every rule --------------------
                    st0 = time.perf_counter()
                    f_mask = np.ones((m,), dtype=bool)
                    s_mask = np.ones((n,), dtype=bool)
                    step_rules: dict[str, dict] = {}
                    if self.rules and not anchor_ok:
                        # fail-safe: the previous step's certificate was
                        # non-finite, so no region exists — keep every feature
                        # and sample this step (screening degrades to "no
                        # speedup", never to a wrong discard) and record the
                        # refusal
                        health[k] |= HEALTH_SCREEN_REFUSED
                    elif self.rules:
                        region = ConvexRegion.build(
                            y, lam_prev, lam, theta_prev, delta=delta_prev,
                            w1=jnp.asarray(w_host, X.dtype), b1=b_host,
                            dw=dw_pred, db=db_pred,
                        )
                        for rule in feature_rules:
                            rb = rule.bounds(X, y, region)
                            rk = np.asarray(rule.keep(rb))
                            f_mask &= rk
                            rb_np = np.asarray(rb, np.float64)
                            step_rules[rule.name] = {
                                "kept": int(rk.sum()),
                                "bound_mean": (float(rb_np.mean())
                                               if rb_np.size else 0.0),
                            }
                        for rule in sample_rules:
                            s_mask &= np.asarray(
                                rule.keep(rule.bounds(X, y, region)))
                    s_times[k] = time.perf_counter() - st0
                    rule_log.append(step_rules)

                    f_idx = np.nonzero(f_mask)[0]
                    kept[k] = len(f_idx)
                    screen_span.set(kept=int(kept[k]))

                with span("path.solve", step=k) as solve_span:
                    # -- solve + verification loop ----------------------------
                    warm = {"w": w_host, "b": b_host, "rounds": 0}

                    skw = None
                    if dyn_sample_rule is not None:
                        # the in-solver sample screen uses the same slack model
                        # the rule screens with between lambdas: the driver's
                        # trust radii plus the secant anchored at this step's
                        # margins (rule.bounds above just updated _u_prev to
                        # them)
                        skw = dict(
                            dynamic_samples=True,
                            sample_dw=dw_pred, sample_db=db_pred,
                            sample_u_prev=dyn_sample_rule._u_prev,
                            sample_shrink_factor=dyn_sample_rule.shrink_factor,
                            sample_margin_floor=dyn_sample_rule.margin_floor,
                        )

                    def solve(mask):
                        s_idx = np.nonzero(mask)[0]
                        # in-solver sample screening only on the first round: a
                        # verification re-solve must not re-drop the violators
                        # it was asked to re-admit
                        res, w_full = self._solve_reduced(
                            X, y, X_np, lam, f_mask, f_idx, mask, s_idx,
                            warm["w"], warm["b"], L_path,
                            sample_screen_kw=(skw if warm["rounds"] == 0
                                              else None),
                        )
                        warm["w"], warm["b"] = w_full, float(res.b)
                        warm["rounds"] += 1
                        if getattr(res, "sample_mask", None) is not None:
                            # fold the in-solver drops into the step's screened
                            # set so the verification pass below covers them
                            # too
                            mask &= np.asarray(res.sample_mask)
                        return res, w_full, float(res.b)

                    res, w_full, b_new, rounds = solve_with_verification(
                        solve, sample_rules, X_np, y_np, s_mask,
                        max_rounds=self.max_verify_rounds,
                    )

                    kept_s[k] = int(s_mask.sum())
                    vrounds[k] = rounds
                    if sample_rules:
                        sample_masks[k] = s_mask.copy()
                    if isinstance(res, DynamicFistaResult):
                        dyn_log[k] = _dynamic_telemetry(res)
                    if getattr(res, "health", None) is not None:
                        health[k] |= int(res.health)

                    if self._fault_injector is not None:
                        w_full, b_new = self._fault_injector(k, w_full, b_new)

                    # -- movement estimates for the next step's trust region --
                    # (weights[k-1]/biases[k-1] hold the previous accepted
                    # solution; at k=1 that is the closed form w=0, b=b* at
                    # lam_max)
                    dw_pred = self.shrink_factor * float(
                        np.linalg.norm(w_full - weights[k - 1]))
                    db_pred = self.shrink_factor * abs(b_new - biases[k - 1])

                    b_host = b_new
                    w_host = w_full.copy()
                    solve_span.set(iters=int(res.n_iters))

                with span("path.certify", step=k):
                    ct0 = time.perf_counter()
                    theta_prev, delta_prev = safe_theta_and_delta(
                        X, y, jnp.asarray(w_full, X.dtype),
                        jnp.asarray(b_host, X.dtype),
                        jnp.asarray(lam),
                    )
                    anchor_ok = _anchor_ok(theta_prev, delta_prev)
                    deltas_log[k] = float(delta_prev)
                    lam_prev = lam

                    weights[k] = w_full
                    biases[k] = b_host
                    objectives[k] = float(res.obj)
                    active[k] = int(np.sum(np.abs(w_full) > 1e-10))
                    iters[k] = int(res.n_iters)
                    # the certificate dispatch above is async — block so the
                    # step's wall time covers all device work it caused, not
                    # just what the host happened to wait for
                    jax.block_until_ready((theta_prev, delta_prev))
                    c_times[k] = time.perf_counter() - ct0
                wall[k] = time.perf_counter() - t0
                step_span.set(kept=int(kept[k]), active=int(active[k]))

                # telemetry hand-back: rules exposing ``observe`` (AutoRule's
                # cost model) learn this step's solve wall per kept feature
                solve_s = max(wall[k] - s_times[k], 0.0)
                for rule in feature_rules:
                    obs = getattr(rule, "observe", None)
                    if obs is not None:
                        obs(solve_seconds=solve_s, kept=int(kept[k]))

        kept_s[0] = 0
        self._observe_run("host", kept, health)
        path_trace = build_path_trace(
            "host", lambdas, kept, kept_s, active, iters, wall,
            deltas=deltas_log, health=health, screen_s=s_times,
            solve_s=np.maximum(wall - s_times - c_times, 0.0),
            certify_s=c_times, walls_observed=True,
            meta={"reduce": self.reduce, "lam_max": lam_max_val},
        )
        return PathResult(
            lambdas=lambdas, weights=weights, biases=biases, objectives=objectives,
            kept=kept, active=active, solver_iters=iters, wall_times=wall,
            screen_times=s_times, screened=bool(self.rules),
            kept_samples=kept_s, verify_rounds=vrounds,
            rules=tuple(r.name for r in self.rules),
            extras={"lam_max": lam_max_val, "sample_masks": sample_masks,
                    "dynamic": dyn_log, "rule_telemetry": rule_log,
                    "health": health, "path_trace": path_trace},
        )

    @staticmethod
    def _observe_run(engine: str, kept, health):
        """Fold one run's per-step telemetry into the process metrics
        registry (``repro.obs.metrics``): step counts, guard-tripped
        steps, and the kept-per-step distribution."""
        obs_metrics.counter("path.steps").inc(int(len(kept)))
        obs_metrics.counter("path.guard_trips").inc(
            int(np.count_nonzero(np.asarray(health))))
        h = obs_metrics.histogram("path.kept")
        for v in np.asarray(kept):
            h.observe(float(v))

    # -- one reduced solve -------------------------------------------------

    def _solve_reduced(self, X, y, X_np, lam, f_mask, f_idx, s_mask, s_idx,
                       w_host, b_host, L=None, sample_screen_kw=None):
        """Reduce X on both axes per self.reduce, solve, scatter w back.

        ``L``: the path-shared Lipschitz upper bound (valid for any
        reduction of X; None re-estimates on the reduced matrix).
        ``sample_screen_kw``: in-solver dynamic sample re-screen options
        (mask mode only — gathered sample axes reindex the mask)."""
        m, n = X.shape
        screening_f = len(f_idx) < m
        screening_s = len(s_idx) < n
        dtype = X_np.dtype

        if self.reduce == "gather" and (screening_f or screening_s):
            sel_f, valid_f = self._feature_select(X_np, f_idx, m)
            pad_n = min(_bucket(max(len(s_idx), 1)), n) if screening_s else n
            sel_s = np.zeros((pad_n,), dtype=np.int64)
            sel_s[: len(s_idx)] = s_idx if screening_s else np.arange(n)
            valid_s = np.arange(pad_n) < (len(s_idx) if screening_s else n)

            Xr = X_np[np.ix_(sel_f, sel_s)]
            # zero padded rows AND columns: padding must not distort the
            # Lipschitz estimate (duplicate columns inflate sigma_max badly)
            Xr = Xr * valid_f[:, None].astype(dtype)
            Xr = Xr * valid_s[None, :].astype(dtype)
            yr = jnp.asarray((np.asarray(y)[sel_s] * valid_s).astype(dtype))
            w0 = jnp.asarray((w_host[sel_f] * valid_f).astype(dtype))
            smask = jnp.asarray(valid_s.astype(dtype)) if screening_s else None
            res = self._solve(jnp.asarray(Xr), yr, lam, w0,
                              jnp.asarray(b_host, X.dtype), smask,
                              feature_mask=jnp.asarray(valid_f.astype(dtype)),
                              L=L)
            w_full = np.zeros((m,), dtype=np.float64)
            w_full[sel_f[: len(f_idx)]] = np.asarray(res.w, np.float64)[: len(f_idx)]
        else:
            Xr = X * jnp.asarray(f_mask[:, None], X.dtype)
            w0 = jnp.asarray((w_host * f_mask).astype(dtype))
            smask = jnp.asarray(s_mask.astype(dtype)) if screening_s else None
            res = self._solve(Xr, y, lam, w0, jnp.asarray(b_host, X.dtype), smask,
                              feature_mask=jnp.asarray(f_mask.astype(dtype)),
                              L=L, sample_screen_kw=sample_screen_kw)
            w_full = np.asarray(res.w, dtype=np.float64) * f_mask

        return res, w_full

    # -- out-of-core lane --------------------------------------------------

    def _run_chunked(self, fc, y, lambdas=None, n_lambdas: int = 10,
                     lam_min_ratio: float = 0.1) -> PathResult:
        """The screened path over ``repro.sparse.FeatureChunked`` storage.

        Same sequential-screening recurrence as :meth:`run`, restructured
        around the device-memory contract: the bound sweep streams X chunk
        by chunk, gather-mode reduction materializes only the rows that
        survive screening (``O(chunk + kept)`` peak device memory), and
        anchor certification streams the correlation sweeps
        (``sparse.gap_theta_delta_stream``).

        Feature screening goes through
        :func:`~repro.sparse.screen_step_stream`: chunk-level gating skips
        the transfer of chunks whose cached stale-anchor bounds certify
        every feature dead (``chunk_skip=True``, the default — see
        :class:`~repro.sparse.ChunkScreenCache`), the pure-VI stack rides
        the bitwise/Pallas-eligible sweep, and any other program-backed
        stack (``edpp``, ``dvi``, ``auto``) evaluates from the same
        streamed anchors (dvi carries history and disables the skip).
        Feature rules without a rule program cannot be streamed and raise.

        Sample rules (:class:`~repro.core.rules.sample_vi.SampleVIRule`,
        alone or inside ``composite``/``sifs`` stacks) run out-of-core via
        the transposed sweep: the margins ``u1 = X^T w1 + b1`` are the
        previous accepted solve's carried ``res.u`` (exact — padded gather
        rows are zero) and ``||x_i||^2`` is the memoized
        :meth:`~repro.sparse.FeatureChunked.col_sq`, so screening costs
        zero extra streams; KKT verification re-checks screened samples
        from the reduced solve's own carried margins (again no stream) and
        re-admits violators exactly like :meth:`run`. The sample axis is
        mask-reduced on the gathered solve (gathering it too would force a
        re-gather per verification round).

        ``dynamic=True`` swaps the gathered in-core solve for the streamed
        segmented :func:`~repro.sparse.fista_solve_chunked`: the step's
        screen seeds the live feature/chunk masks and the solver keeps
        shrinking both every ``screen_every`` iterations from the live
        duality gap — mid-solve transfer volume tracks the certified
        support. Per-step telemetry lands in ``extras["dynamic"]``.
        """
        from repro.sparse import (  # lazy: repro.sparse imports core.solver
            ChunkScreenCache,
            fista_solve_chunked,
            gap_theta_delta_stream,
            lambda_max_stream,
            lipschitz_estimate_stream,
            screen_step_stream,
        )
        from .rules.programs import PROGRAMS
        from .rules.sample_vi import margin_surplus_core, violators_from_margins

        if self.reduce != "gather":
            raise ValueError(
                "chunked storage implies gather-mode reduction (mask mode "
                f"would build the full (m, n) device matrix), got "
                f"reduce={self.reduce!r}"
            )
        feature_rules = [r for r in self.rules if r.axis == AXIS_FEATURES]
        sample_rules = [r for r in self.rules if r.axis == AXIS_SAMPLES]
        bad = [r.name for r in feature_rules
               if getattr(r, "program", None) not in PROGRAMS]
        if bad:
            raise ValueError(
                f"chunked storage streams program-backed feature rule "
                f"bounds only ({tuple(sorted(PROGRAMS))}); feature rule(s) "
                f"{bad} have no rule program — use in-core storage"
            )
        bad_s = [r.name for r in sample_rules
                 if not isinstance(r, SampleVIRule)]
        if bad_s:
            raise ValueError(
                f"chunked storage verifies sample rules from the solver's "
                f"carried margins; only SampleVIRule(-derived) rules "
                f"qualify, got {bad_s}"
            )
        progs = tuple(dict.fromkeys(r.program for r in feature_rules))
        needs_hist = any(PROGRAMS[p].n_anchors > 1 for p in progs)
        anchor_old = None  # streamed AnchorStats of the step-before-last
        cache = ChunkScreenCache(fc)

        y = jnp.asarray(y)
        y_np = np.asarray(y)
        yd = jnp.asarray(y, fc.dtype)
        m, n = fc.shape
        tau = min((r.tau for r in feature_rules if hasattr(r, "tau")),
                  default=SAFE_TAU)
        dyn_kw = (dict(screen_every=self.screen_every,
                       screen_tau=dynamic_tau(self.rules))
                  if self.dynamic else {})

        if self.L is not None:
            L_path = jnp.asarray(self.L, fc.dtype)
        else:
            L_path = (None if self.exact_lipschitz
                      else lipschitz_estimate_stream(fc))
        lam_max_val = float(lambda_max_stream(fc, y))
        if lambdas is None:
            lambdas = default_lambda_grid(lam_max_val, n_lambdas, lam_min_ratio)
        lambdas = _validate_grid(lambdas)
        T = len(lambdas)

        weights = np.zeros((T, m), dtype=np.float64)
        biases = np.zeros((T,), dtype=np.float64)
        objectives = np.zeros((T,), dtype=np.float64)
        kept = np.zeros((T,), dtype=np.int64)
        kept_s = np.zeros((T,), dtype=np.int64)
        vrounds = np.zeros((T,), dtype=np.int64)
        active = np.zeros((T,), dtype=np.int64)
        iters = np.zeros((T,), dtype=np.int64)
        wall = np.zeros((T,), dtype=np.float64)
        s_times = np.zeros((T,), dtype=np.float64)
        c_times = np.zeros((T,), dtype=np.float64)  # certification walls
        deltas_log = np.full((T,), np.nan, dtype=np.float64)
        health = np.zeros((T,), dtype=np.int64)  # guard telemetry per step
        live_log = np.full((T,), fc.n_chunks, dtype=np.int64)
        sample_masks: dict[int, np.ndarray] = {}
        dyn_log: dict[int, dict] = {}

        if sample_rules:
            x_sq = fc.col_sq()  # transposed sweep, once per container
            for rule in sample_rules:
                rule._u_prev = None  # prepare() needs in-core X; reset here
        # trust-region movement state + carried margins of the accepted
        # solution (X^T w, bias excluded) for the sample rules' u1
        dw_pred = float("inf")
        db_pred = float("inf")
        u_carry = np.zeros((n,), dtype=np.float64)

        lam_prev = float(lambdas[0])
        w_host = np.zeros((m,), dtype=np.float64)
        if lambdas[0] >= lam_max_val * (1.0 - 1e-9):
            b_host = float(bias_at_lambda_max(y))
            theta_prev = theta_at_lambda_max(y, jnp.asarray(lambdas[0]))
            delta_prev = jnp.asarray(0.0, jnp.asarray(y).dtype)
            biases[0] = b_host
            xi0 = np.maximum(0.0, 1.0 - y_np * b_host)
            objectives[0] = 0.5 * float(np.sum(xi0 * xi0))
        else:
            # grid starts below lambda_max: streamed unscreened solve, then
            # gap-certify (the closed form does not hold — cf. run())
            t0 = time.perf_counter()
            rep0: dict = {}
            res0 = fista_solve_chunked(
                fc, y, float(lambdas[0]), max_iters=self.max_iters,
                tol=self.tol, L=L_path, guards=self.guards,
                report=rep0 if self.dynamic else None, **dyn_kw,
            )
            jax.block_until_ready(res0.w)
            wall[0] = time.perf_counter() - t0
            w_host = np.asarray(res0.w, dtype=np.float64)
            b_host = float(res0.b)
            weights[0] = w_host
            biases[0] = b_host
            objectives[0] = float(res0.obj)
            kept[0] = m
            active[0] = int(np.sum(np.abs(w_host) > 1e-10))
            iters[0] = int(res0.n_iters)
            u_carry = np.asarray(res0.u, dtype=np.float64)
            if self.dynamic:
                dyn_log[0] = rep0
            if getattr(res0, "health", None) is not None:
                health[0] |= int(res0.health)
            theta_prev, delta_prev, d_th0 = gap_theta_delta_stream(
                fc, y, jnp.asarray(w_host, fc.dtype), res0.b,
                jnp.asarray(float(lambdas[0])), u=res0.u, want_corr=True,
            )
            if feature_rules:
                cache.refresh(anchor_stats(
                    yd, float(lambdas[0]), theta_prev, delta_prev, d_th0))
        anchor_ok = _anchor_ok(theta_prev, delta_prev)
        deltas_log[0] = float(delta_prev)

        for k in range(1, T):
            lam = float(lambdas[k])
            with span("path.step", step=k, lam=lam) as step_span:
                t0 = time.perf_counter()

                with span("path.screen", step=k) as screen_span:
                    st0 = time.perf_counter()
                    s_mask = np.ones((n,), dtype=bool)
                    live = np.ones((fc.n_chunks,), dtype=bool)
                    if feature_rules and not anchor_ok:
                        # fail-safe: no finite certificate to screen from —
                        # keep every feature and stream every chunk this step
                        # (cf. run())
                        health[k] |= HEALTH_SCREEN_REFUSED
                        f_mask = np.ones((m,), dtype=bool)
                    elif feature_rules:
                        keep_m, _, anchor, live = screen_step_stream(
                            fc, y, lam_prev, lam, theta_prev, delta=delta_prev,
                            rules=progs, tau=tau, cache=cache,
                            anchor_old=anchor_old, skip=self.chunk_skip,
                            use_pallas=self.use_pallas,
                        )
                        if needs_hist:
                            # last step's fresh anchor is this step's old —
                            # free
                            anchor_old = anchor
                        f_mask = np.asarray(keep_m)
                        live_log[k] = int(live.sum())
                    else:
                        f_mask = np.ones((m,), dtype=bool)
                    if sample_rules:
                        # transposed sweep: margins + column norms, zero
                        # streams
                        u1 = (jnp.asarray(u_carry, fc.dtype)
                              + jnp.asarray(b_host, fc.dtype))
                        for rule in sample_rules:
                            surplus = margin_surplus_core(
                                u1, yd, x_sq, dw_pred, db_pred,
                                u_prev=rule._u_prev,
                                shrink_factor=rule.shrink_factor,
                                margin_floor=rule.margin_floor,
                            )
                            # secant anchor for the next step
                            rule._u_prev = u1
                            # NaN-safe drop test (cf. solver._dynamic_run): a
                            # non-finite surplus keeps the sample — a poisoned
                            # margin costs verification rounds, never loss
                            # terms
                            s_mask &= np.asarray(~(surplus >= 0.0))
                    s_times[k] = time.perf_counter() - st0

                    f_idx = np.nonzero(f_mask)[0]
                    kept[k] = len(f_idx)
                    screen_span.set(kept=int(kept[k]))

                with span("path.solve", step=k) as solve_span:
                    # -- solve + sample verification (cf.
                    # solve_with_verification):
                    # the feature mask is a-priori safe and fixed for the step,
                    # so the gather happens once; only the sample mask changes
                    # per verification round, and the margin re-check rides the
                    # solve's own carried u — no extra stream either way.
                    if not self.dynamic:
                        sel_f, valid_f = self._feature_select(None, f_idx, m)
                        Xr = jnp.asarray(fc.gather_rows(sel_f)
                                         * valid_f[:, None].astype(fc.dtype))
                    warm_w, warm_b = w_host, b_host
                    rounds = 0
                    while True:
                        smask_dev = (None if s_mask.all()
                                     else jnp.asarray(s_mask.astype(fc.dtype)))
                        if self.dynamic:
                            rep: dict = {}
                            res = fista_solve_chunked(
                                fc, y, lam,
                                w0=jnp.asarray(
                                    (warm_w * f_mask).astype(fc.dtype)),
                                b0=jnp.asarray(warm_b, fc.dtype),
                                max_iters=self.max_iters, tol=self.tol,
                                L=L_path, sample_mask=smask_dev,
                                feature_mask=f_mask,
                                report=rep, guards=self.guards, **dyn_kw,
                            )
                            w_full = np.asarray(res.w, dtype=np.float64)
                            dyn_log[k] = rep
                        else:
                            w0 = jnp.asarray(
                                (warm_w[sel_f] * valid_f).astype(fc.dtype))
                            res = fista_solve(
                                Xr, y, jnp.asarray(lam), w0=w0,
                                b0=jnp.asarray(warm_b, fc.dtype),
                                max_iters=self.max_iters, tol=self.tol,
                                L=L_path, sample_mask=smask_dev,
                                use_pallas=self.use_pallas,
                                guards=self.guards,
                            )
                            w_full = np.zeros((m,), dtype=np.float64)
                            w_full[sel_f[: len(f_idx)]] = (
                                np.asarray(res.w, np.float64)[: len(f_idx)])
                        b_new = float(res.b)
                        warm_w, warm_b = w_full, b_new
                        if s_mask.all() or not sample_rules:
                            break
                        scr = np.nonzero(~s_mask)[0]
                        u_np = np.asarray(res.u, dtype=np.float64)
                        viol = np.asarray(violators_from_margins(
                            y_np, u_np[scr] + b_new, scr))
                        if len(viol) == 0:
                            break
                        rounds += 1
                        if rounds >= self.max_verify_rounds:
                            s_mask[:] = True  # give up screening: exact solve
                        else:
                            s_mask[viol] = True

                    kept_s[k] = int(s_mask.sum())
                    vrounds[k] = rounds
                    if sample_rules:
                        sample_masks[k] = s_mask.copy()
                    if getattr(res, "health", None) is not None:
                        health[k] |= int(res.health)

                    if self._fault_injector is not None:
                        w_full, b_new = self._fault_injector(k, w_full, b_new)

                    # movement estimates for the next step's trust region
                    dw_pred = self.shrink_factor * float(
                        np.linalg.norm(w_full - weights[k - 1]))
                    db_pred = self.shrink_factor * abs(b_new - biases[k - 1])
                    b_host = b_new
                    w_host = w_full
                    u_carry = np.asarray(res.u, dtype=np.float64)

                    # certify the accepted point as the next anchor. The margin
                    # sweep rides the solver's carried u (exact: padding rows
                    # are zero); the correlation sweeps stream only the
                    # gating-live chunks — every kept feature lives in one
                    # (dead chunks' stamped bounds are all < tau), so the
                    # reduced-problem feasibility max is exact — and the final
                    # sweep doubles as the fresh d_theta that re-anchors every
                    # live chunk's cache entry: next step's gating is exactly
                    # as sharp as its screen, at zero extra streams.
                    live_arg = None if live.all() else live
                    fm_cert = (None if f_mask.all()
                               else jnp.asarray(f_mask.astype(fc.dtype)))
                    solve_span.set(iters=int(res.n_iters))
                with span("path.certify", step=k):
                    ct0 = time.perf_counter()
                    theta_prev, delta_prev, d_th = gap_theta_delta_stream(
                        fc, y, jnp.asarray(w_full, fc.dtype), res.b,
                        jnp.asarray(lam), u=res.u, live_chunks=live_arg,
                        feature_mask=fm_cert, want_corr=True,
                    )
                    anchor_ok = _anchor_ok(theta_prev, delta_prev)
                    deltas_log[k] = float(delta_prev)
                    if feature_rules:
                        # a poisoned anchor is safe to hand over: refresh()
                        # guards non-finite stats by *invalidating* the touched
                        # entries, so gating treats those chunks as
                        # never-streamed (always live)
                        cache.refresh(
                            anchor_stats(yd, lam, theta_prev, delta_prev,
                                         d_th),
                            live=set(int(ci) for ci in np.nonzero(live)[0]),
                        )
                    lam_prev = lam

                    weights[k] = w_full
                    biases[k] = b_host
                    objectives[k] = float(res.obj)
                    active[k] = int(np.sum(np.abs(w_full) > 1e-10))
                    iters[k] = int(res.n_iters)
                    jax.block_until_ready((theta_prev, delta_prev))
                    c_times[k] = time.perf_counter() - ct0
                wall[k] = time.perf_counter() - t0
                step_span.set(kept=int(kept[k]), active=int(active[k]),
                          live_chunks=int(live_log[k]))

        kept_s[0] = 0
        self._observe_run("chunked", kept, health)
        path_trace = build_path_trace(
            "chunked", lambdas, kept, kept_s, active, iters, wall,
            deltas=deltas_log, health=health, screen_s=s_times,
            solve_s=np.maximum(wall - s_times - c_times, 0.0),
            certify_s=c_times, walls_observed=True,
            meta={"storage": "chunked", "n_chunks": fc.n_chunks,
                  "chunk_skip": self.chunk_skip, "lam_max": lam_max_val,
                  "stream_stats": dict(fc.stats)},
        )
        extras = {"lam_max": lam_max_val, "storage": "chunked",
                  "n_chunks": fc.n_chunks, "chunk_skip": self.chunk_skip,
                  "live_chunks": live_log,
                  "health": health,
                  "path_trace": path_trace,
                  "stream_stats": dict(fc.stats)}
        if sample_rules:
            extras["sample_masks"] = sample_masks
        if self.dynamic:
            extras["dynamic"] = dyn_log
        return PathResult(
            lambdas=lambdas, weights=weights, biases=biases,
            objectives=objectives, kept=kept, active=active,
            solver_iters=iters, wall_times=wall, screen_times=s_times,
            screened=bool(self.rules),
            kept_samples=kept_s,
            verify_rounds=vrounds,
            rules=tuple(r.name for r in self.rules),
            extras=extras,
        )


def _entry(fn):
    """Run the public entry in the ``svm_path`` span, and observe its self
    time into ``path.entry_s``: the call's wall less the single-dispatch
    engines' dispatch wall (``extras["total_seconds"]``, the
    ``svm_path.dispatch`` span), so what the entry costs on the host around
    the device program. The host engine has no single dispatch and
    observes nothing."""
    @functools.wraps(fn)
    def entry(*args, **kwargs):
        t0 = time.perf_counter()
        with span("svm_path"):
            r = fn(*args, **kwargs)
        wall_s = time.perf_counter() - t0
        first = r[0] if isinstance(r, list) else r
        dispatch_s = first.extras.get("total_seconds")
        if dispatch_s is not None:
            obs_metrics.histogram("path.entry_s").observe(
                wall_s - dispatch_s)
        return r

    return entry


@_entry
def svm_path(
    X: jax.Array,
    y: jax.Array,
    lambdas: Optional[Sequence[float]] = None,
    n_lambdas: int = 10,
    lam_min_ratio: float = 0.1,
    screening: bool = True,
    reduce: Optional[str] = None,
    tol: float = 1e-9,
    max_iters: int = 4000,
    tau: float = SAFE_TAU,
    rules=None,
    dynamic: bool = False,
    screen_every: int = 50,
    engine: str = "host",
    exact_lipschitz: bool = False,
    use_pallas: Optional[bool] = None,
    chunk_skip: bool = True,
    guards: Optional[bool] = None,
) -> PathResult:
    """Solve the L1-L2-SVM path with configurable screening rules.

    Back-compatible wrapper over :class:`PathDriver`: ``screening=True``
    defaults to the paper's feature rule (with ``tau``); pass ``rules=``
    (``"sample_vi"``, ``"composite"``, a list, or instances) to choose
    other reductions. ``screening=False`` (or ``rules=[]``) disables all.
    ``dynamic=True`` additionally re-screens inside each FISTA solve every
    ``screen_every`` iterations (see :class:`PathDriver`). ``chunk_skip``
    (chunked storage only) gates whole feature-row chunks off the stream
    from their cached stale-anchor bounds (see :class:`PathDriver`).

    ``engine`` selects the execution strategy:

    * ``"host"`` — this driver: per-step host orchestration, gather/mask
      reduction on both axes, any rule mix, sample-rule verification;
    * ``"scan"`` — ``core/path_scan.py``: the whole path as one jitted
      ``lax.scan`` program (a-priori-safe feature rules only — any
      program-backed stack such as ``"feature_vi"``, ``"edpp"``, ``"dvi"``
      or a list of them; mask or compact reduction, zero host round
      trips). Sample rules raise at dispatch. See that module for the
      trade-off discussion.
    * ``"batched"`` — ``path_scan.svm_path_batched``: B paths as one
      program (``X (B, m, n)`` independent problems, or ``X (m, n)`` with
      ``lambdas (B, T)`` grids). Same program-backed feature-rule stacks
      as ``"scan"``; returns a *list* of ``PathResult``. Compact reduction
      composes with batching through the shared-cap schedule. For ragged
      many-job workloads prefer ``launch/path_server.py`` (continuous
      batching over these programs).

    ``reduce`` defaults per engine (host: ``"gather"``, scan/batched:
    ``"mask"``). Rule of thumb — **gather** (host) for multiplicative
    feature x sample reduction and verified sample rules; **mask**
    (any engine) when screening is weak, so compaction would only add
    gather traffic; **compact** (scan/batched) when screening certifies a
    small active set and the solve should cost FLOPs proportional to it
    (see ``path_scan.py``'s module docstring for the batched shared-cap
    composition).
    """
    if engine == "scan":
        from .path_scan import svm_path_scan  # deferred: path_scan imports us

        if _is_chunked(X):
            raise ValueError(
                "engine='scan' jit-compiles over an in-core X; chunked "
                "storage runs on the host engine (engine='host', the "
                "default when X is a FeatureChunked)"
            )
        # rule-spec lowerability is validated at dispatch by
        # path_scan._static_opts -> rules/programs.resolve_programs:
        # sample rules / verification-needing specs raise there
        return svm_path_scan(
            X, y, lambdas=lambdas, n_lambdas=n_lambdas,
            lam_min_ratio=lam_min_ratio, screening=screening, tau=tau,
            tol=tol, max_iters=max_iters, dynamic=dynamic,
            screen_every=screen_every, use_pallas=use_pallas,
            exact_lipschitz=exact_lipschitz,
            reduce="mask" if reduce is None else reduce,
            rules=rules, guards=guards,
        )
    if engine == "batched":
        from .path_scan import svm_path_batched  # deferred: imports us

        if _is_chunked(X):
            raise ValueError(
                "engine='batched' jit-compiles over in-core arrays; chunked "
                "storage runs on the host engine"
            )
        return svm_path_batched(
            X, y, lambdas=lambdas, n_lambdas=n_lambdas,
            lam_min_ratio=lam_min_ratio, screening=screening, tau=tau,
            tol=tol, max_iters=max_iters, dynamic=dynamic,
            screen_every=screen_every, use_pallas=use_pallas,
            exact_lipschitz=exact_lipschitz,
            reduce="mask" if reduce is None else reduce,
            rules=rules, guards=guards,
        )
    if engine != "host":
        raise ValueError(
            f"engine must be 'host', 'scan', or 'batched', got {engine!r}")
    if rules is None:
        rules = [FeatureVIRule(tau=tau)] if screening else []
    driver = PathDriver(rules=rules,
                        reduce="gather" if reduce is None else reduce,
                        tol=tol, max_iters=max_iters,
                        dynamic=dynamic, screen_every=screen_every,
                        exact_lipschitz=exact_lipschitz, use_pallas=use_pallas,
                        chunk_skip=chunk_skip, guards=guards)
    return driver.run(X, y, lambdas=lambdas, n_lambdas=n_lambdas,
                      lam_min_ratio=lam_min_ratio)
