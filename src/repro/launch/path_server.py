"""Multi-tenant sparse-SVM path server: continuous batching of screened paths.

The paper's pitch is throughput — screening makes many solves (a lambda
path per tenant, a hyperparameter sweep, one model per dataset) cost far
less than their naive FLOPs. This module is the serving front end for that
claim: a queue of :class:`PathJob` requests drains through a fixed number
of batch *slots*, every lambda step of every resident job executes inside
ONE jitted program (``core/path_scan._batched_path_step`` — the shared-cap
batched screen/solve/certify step), and a slot refills the moment its job's
grid is exhausted (continuous batching, the loop shape of
``launch/serve.py::BatchedServer``). Results stream back per lambda step;
a finished job's :class:`~repro.core.path.PathResult` is assembled from its
streamed steps, so no job waits on the batch.

Bucket / padding policy
-----------------------
Jobs are padded into power-of-two shape buckets (``core/path.py::_bucket``,
min 8): a job with true shape ``(m, n)`` occupies a ``(m_b, n_b)`` slot
with ``m_b = bucket(m)``, ``n_b = bucket(n)``. Padding is *safe by
construction*, not cosmetic:

* padded **feature rows** are zero, so their screen bound is 0 < tau and
  sequential screening certifiably drops them at every step — under
  ``reduce="compact"`` they cost nothing in the solve;
* padded **sample columns** carry a 0/1 ``sample_mask`` threaded through
  the solver, the certificate, and the hoisted screen reductions
  (``n_tot`` is the live count), so each slot solves its *true, unpadded*
  problem to solver resolution.

Slots in one batch share a bucket, so a serve group is keyed by
``(m_b, n_b, rule_stack, dynamic)`` — ``rule_stack`` the job's rule spec
resolved to a scan-lowerable program tuple (any single-anchor stack:
``feature_vi``, ``edpp``, ``auto``, lists; ``()`` = screening off; ``dvi``
is rejected because anchor *history* cannot ride a slot carry that jobs
splice in and out of); the queue drains group by group
(a job from a different bucket waits for the current group's slots to
empty rather than forcing a recompile mid-group).

Program-cache key anatomy
-------------------------
Compiled step programs live in an explicit warm cache keyed by::

    (m_bucket, n_bucket, cap_bucket, B, engine_config)

``m_bucket``/``n_bucket``  padded slot shape (above);
``cap_bucket``             the shared compact capacity for this step —
                           predicted per sub-batch from the jobs' observed
                           keep counts via ``compact_caps_batched`` (equal
                           to ``m_bucket`` for mask-mode steps, so mask and
                           compact steps are distinct programs);
``B``                      the slot count (batch width of the program);
``engine_config``          the hashable ``(name, value)`` static-option
                           tuple (max_iters, screening, dynamic, ...).

A cache hit dispatches with zero tracing; misses compile once per key
(a handful per bucket ladder); ``cache_stats()`` exposes hits / misses /
retraces (a retrace = jit holding more than one trace for a cached
program — a same-key same-shape dispatch that retraced is a regression).
Under-predicting the capacity never breaks correctness: the step program's
scalar overflow check demotes that step to its mask branch on device.

Fault tolerance
---------------
Each :class:`PathJob` carries an optional wall-clock ``deadline_s`` and a
``max_retries`` budget. After every batched step the server host-checks each
active slot's outputs for finiteness: a poisoned slot (NaN/inf objective or
weights) is rolled back to its pre-step carry — sanitized, so a poisoned
certificate re-enters as a *refusing* one (``delta = inf`` → the step
fail-safes to keep-all) — and retried with backoff; a slot that exhausts its
retries (or its deadline) is quarantined: masked out of the batch, evicted
with ``status="failed"``, its slot state zeroed, while the other tenants'
slots are untouched. ``serve(..., snapshot_dir=...)`` additionally
checkpoints the whole serve state (device slot buffers, per-job step
streams, queue order) every ``snapshot_every`` steps through
:class:`~repro.checkpoint.manager.CheckpointManager`; re-serving the same
job list with the same ``snapshot_dir`` after a crash resumes mid-path and
produces results equal to an uninterrupted run.

CPU smoke: PYTHONPATH=src python -m repro.launch.path_server --jobs 6
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.core.dual import bias_at_lambda_max, lambda_max, theta_at_lambda_max
from repro.core.linalg import dot
from repro.core.path import PathResult, _bucket, _validate_grid, default_lambda_grid
from repro.core.path_scan import (
    ScanPathOutputs,
    _batched_path_step,
    _static_opts,
    _to_path_result,
    compact_caps_batched,
    engine_cache_info,
)
from repro.core.rules.programs import PROGRAMS, resolve_programs
from repro.core.screening import SAFE_TAU
from repro.core.solver import lipschitz_estimate
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.log import get_logger, setup as log_setup

_LOG = get_logger("launch.path_server")


@dataclass
class PathJob:
    """One tenant's path request: a dataset handle, a grid, and rules."""

    jid: int
    X: np.ndarray                       # (m, n) feature-major design
    y: np.ndarray                       # (n,) ±1 labels
    lambdas: Optional[np.ndarray] = None  # explicit decreasing grid, else:
    n_lambdas: int = 10
    lam_min_ratio: float = 0.1
    rules: str = "feature_vi"           # any single-anchor program stack
    dynamic: bool = False               # in-solver re-screen segments
    deadline_s: Optional[float] = None  # wall budget from first insert
    max_retries: int = 1                # poisoned-step retry budget

    # -- server-owned runtime state (streamed results) ---------------------
    t: int = field(default=0, repr=False)
    steps: list = field(default_factory=list, repr=False)
    result: Optional[PathResult] = field(default=None, repr=False)
    lam_max: float = field(default=0.0, repr=False)
    t_submit: float = field(default=0.0, repr=False)
    t_done: float = field(default=0.0, repr=False)
    t_start: float = field(default=0.0, repr=False)  # deadline epoch
    retries: int = field(default=0, repr=False)
    status: str = field(default="queued", repr=False)  # running/done/failed
    error: Optional[str] = field(default=None, repr=False)

    @property
    def rule_stack(self) -> tuple:
        """The job's rule spec resolved to a scan-lowerable program tuple.

        Raises for sample rules / verification-needing specs (the server
        runs the batched scan step — same lowerability contract as
        ``engine="scan"``) and for two-anchor programs like ``dvi``: the
        slot carry holds exactly one anchor, and jobs splice in and out of
        slots mid-path, so anchor *history* cannot ride the batch carry.
        """
        progs = resolve_programs(self.rules, screening=True)
        deep = [nm for nm in progs if PROGRAMS[nm].n_anchors > 1]
        if deep:
            raise ValueError(
                f"the path server's slot carry holds a single anchor; "
                f"rules needing anchor history {deep} are not servable — "
                f"run {self.rules!r} through engine='scan' or the host "
                f"driver instead"
            )
        return progs

    @property
    def screening(self) -> bool:
        return bool(self.rule_stack)

    def group_key(self) -> tuple:
        """Jobs sharing this key can occupy slots of the same batch."""
        m, n = self.X.shape
        return (_bucket(m), _bucket(n), self.rule_stack, bool(self.dynamic))


class PathServer:
    """Continuous-batching front end over the batched scan-engine step.

    ``slots`` is the batch width B of every compiled step program; see the
    module docstring for the bucket policy and cache-key anatomy.
    ``reduce="compact"`` (default) predicts a shared compact capacity per
    step from observed keep counts; ``reduce="mask"`` always solves
    full-bucket-width.
    """

    def __init__(self, slots: int = 4, *, reduce: str = "compact",
                 tau: float = SAFE_TAU, tol: float = 1e-9,
                 max_iters: int = 4000, screen_every: int = 50,
                 use_pallas: Optional[bool] = None,
                 cap_growth: float = 1.5, dtype=np.float32):
        if reduce not in ("mask", "compact"):
            raise ValueError(f"reduce must be 'mask' or 'compact', got {reduce!r}")
        self.slots = int(slots)
        self.reduce = reduce
        self.tau = float(tau)
        self.tol = float(tol)
        self.max_iters = int(max_iters)
        self.screen_every = int(screen_every)
        self.use_pallas = use_pallas
        self.cap_growth = float(cap_growth)
        self.dtype = np.dtype(dtype)

        self._programs: dict = {}
        self.stats = dict(hits=0, misses=0, steps=0, occupied_slots=0,
                          jobs_done=0, mask_fallback_steps=0,
                          retries=0, jobs_failed=0)
        self._group: Optional[tuple] = None
        self._act = np.zeros((self.slots,), bool)
        self._slot_jobs: list[Optional[PathJob]] = [None] * self.slots
        # testing seam: called as hook(step_count) after every serve-loop
        # step (post-snapshot); raising simulates a server crash mid-drain
        self._step_hook = None
        self._retry_backoff_s = 0.01
        # jobs already finished (done/failed) this serve — snapshots must
        # carry their streams too, or a resume would lose their results
        self._tracked_done: list[PathJob] = []

    def _bump(self, key: str, n: int = 1):
        """Increment a legacy ``stats`` counter and mirror it into the
        process-wide metrics registry under ``serve.<key>``."""
        self.stats[key] += n
        obs_metrics.counter("serve." + key).inc(n)

    # -- program cache -----------------------------------------------------

    def _program(self, m_b: int, n_b: int, cap_b: int, cfg: tuple):
        key = (m_b, n_b, cap_b, self.slots, cfg)
        fn = self._programs.get(key)
        if fn is not None:
            self._bump("hits")
            return fn
        self._bump("misses")
        caps = () if cap_b >= m_b else (cap_b,)
        fn = jax.jit(partial(_batched_path_step, caps=caps, shared_x=False,
                             **dict(cfg)))
        self._programs[key] = fn
        return fn

    def cache_stats(self) -> dict:
        """Warm-cache health: compiled programs, hits/misses, retraces."""
        retraces = sum(max(0, fn._cache_size() - 1)
                       for fn in self._programs.values())
        return dict(programs=len(self._programs), hits=self.stats["hits"],
                    misses=self.stats["misses"], retraces=retraces)

    def metrics(self) -> dict:
        """Unified observability snapshot (the ISSUE's one-stop view):
        the process-wide :mod:`repro.obs.metrics` registry — which the
        server's counters mirror into live — with the step-program cache
        health (:meth:`cache_stats`) and the scan-engine warm-cache layers
        (``engine_cache_info``) absorbed as gauges."""
        obs_metrics.absorb("serve.cache", self.cache_stats())
        info = engine_cache_info()
        obs_metrics.gauge("engine.cache.programs").set(len(info))
        obs_metrics.gauge("engine.cache.retraces").set(
            sum(max(0, v - 1) for v in info.values() if v > 0))
        return obs_metrics.snapshot()

    # -- group (bucket) state ----------------------------------------------

    def _alloc_group(self, group: tuple):
        """(Re)allocate device slot state for a new bucket group."""
        m_b, n_b, rule_stack, dynamic = group
        B, dt = self.slots, self.dtype
        self._group = group
        # the resolved program tuple re-resolves identically (names are
        # canonical), so it feeds _static_opts as the rules spec directly
        self._cfg = _static_opts(self.max_iters, bool(rule_stack), dynamic,
                                 self.screen_every, self.use_pallas,
                                 False, self.reduce,
                                 list(rule_stack) if rule_stack else "none")
        # _batched_path_step takes the option subset without `reduce` —
        # the reduction is carried by the caps tuple in the program key
        self._step_cfg = tuple(kv for kv in self._cfg if kv[0] != "reduce")
        z = lambda *s: jnp.zeros(s, dt)
        self._X = z(B, m_b, n_b)
        self._y = z(B, n_b)
        self._sm = z(B, n_b)
        self._statics = (z(B, m_b), z(B, m_b), z(B, m_b), z(B), z(B))
        self._inv_L = jnp.ones((B,), dt)
        self._carry = (z(B, m_b), z(B), z(B, n_b), z(B),
                       jnp.ones((B,), dt), jnp.ones((B, m_b), dt))
        self._lam_host = np.ones((B,), np.float64)
        self._last_kept = np.zeros((B,), np.int64)
        self._act[:] = False
        self._slot_jobs = [None] * B

    def _insert(self, slot: int, job: PathJob):
        """Pad the job into its bucket and splice it into device state."""
        m_b, n_b, _, _ = self._group
        m, n = job.X.shape
        dt = self.dtype
        Xp = np.zeros((m_b, n_b), dt)
        Xp[:m, :n] = job.X
        yp = np.zeros((n_b,), dt)
        yp[:n] = job.y
        smp = np.zeros((n_b,), dt)
        smp[:n] = 1.0

        # anchors on the TRUE arrays with the repo's closed forms (eager,
        # device dtype — matching what the scan engines compute)
        Xj = jnp.asarray(job.X.astype(dt))
        yj = jnp.asarray(job.y.astype(dt))
        job.lam_max = float(lambda_max(Xj, yj))
        if job.lambdas is None:
            job.lambdas = default_lambda_grid(job.lam_max, job.n_lambdas,
                                              job.lam_min_ratio)
        job.lambdas = _validate_grid(job.lambdas)
        b0 = bias_at_lambda_max(yj)
        th0 = np.zeros((n_b,), dt)
        th0[:n] = np.asarray(
            theta_at_lambda_max(yj, jnp.asarray(job.lam_max, dt)))

        Xpj = jnp.asarray(Xp)
        ypj = jnp.asarray(yp)
        smj = jnp.asarray(smp)
        # padded rows/cols are zero, so sigma_max is the true problem's
        L = jnp.maximum(lipschitz_estimate(Xpj) * 1.01, 1e-12)
        # hoisted screen reductions (path_scan._batched_statics, per slot)
        d_one = dot(Xpj, ypj)
        d_y = dot(Xpj, smj)
        d_sq = dot(Xpj * Xpj, smj)
        one_y = jnp.sum(ypj * smj)
        n_tot = jnp.sum(smj)

        at = lambda a, v: a.at[slot].set(v)
        self._X = at(self._X, Xpj)
        self._y = at(self._y, ypj)
        self._sm = at(self._sm, smj)
        s = self._statics
        self._statics = (at(s[0], d_one), at(s[1], d_y), at(s[2], d_sq),
                         at(s[3], one_y), at(s[4], n_tot))
        self._inv_L = at(self._inv_L, 1.0 / L)
        c = self._carry
        self._carry = (
            at(c[0], jnp.zeros((m_b,), dt)),
            at(c[1], jnp.asarray(b0, dt)),
            at(c[2], jnp.asarray(th0)),
            at(c[3], jnp.asarray(0.0, dt)),
            at(c[4], jnp.asarray(job.lam_max, dt)),
            at(c[5], jnp.ones((m_b,), dt)),
        )
        self._lam_host[slot] = job.lam_max
        self._last_kept[slot] = 0
        self._act[slot] = True
        self._slot_jobs[slot] = job
        job.status = "running"
        if job.t_start == 0.0:
            job.t_start = time.perf_counter()

    # -- one batched lambda step -------------------------------------------

    def _predict_cap(self, m_b: int) -> int:
        """Shared capacity for the next step from observed keep counts.

        Keep counts grow as lambda decreases, so the last observed count
        times ``cap_growth`` headroom feeds the shared-cap schedule. A
        fresh job (no observation yet) predicts the smallest bucket — its
        first step past lambda_max keeps almost nothing. Wrong predictions
        cost speed, never correctness (on-device overflow fallback).
        """
        if self.reduce != "compact":
            return m_b
        pred = [max(1, int(np.ceil(self._last_kept[s] * self.cap_growth)))
                for s in range(self.slots) if self._act[s]]
        return int(compact_caps_batched(m_b, pred or [1]))

    def step(self):
        m_b, n_b, _, _ = self._group
        now = time.perf_counter()
        for s in range(self.slots):
            job = self._slot_jobs[s]
            if not self._act[s]:
                continue
            if (job.deadline_s is not None
                    and now - job.t_start > job.deadline_s):
                self._evict_failed(
                    s, f"deadline {job.deadline_s}s exceeded at "
                       f"lambda index {job.t}")
                continue
            self._lam_host[s] = float(job.lambdas[job.t])
        if not self._act.any():
            return
        cap_b = self._predict_cap(m_b)
        fn = self._program(m_b, n_b, cap_b, self._step_cfg)
        lam = jnp.asarray(self._lam_host, self.dtype)
        act = jnp.asarray(self._act)
        tau = jnp.asarray(self.tau, self.dtype)
        carry_prev = self._carry  # functional updates: free pre-step copy
        with obs_trace.span("serve.dispatch", slots=self.slots):
            self._carry, out = fn(self._X, self._y, self._sm, self._statics,
                                  self._inv_L, tau, self.tol, carry_prev,
                                  lam, act)
            host = {k: np.asarray(v) for k, v in out._asdict().items()}
        self._bump("steps")
        self._bump("occupied_slots", int(self._act.sum()))
        if self.reduce == "compact" and int(host["cap"][0]) >= m_b:
            self._bump("mask_fallback_steps")
        for s in range(self.slots):
            if not self._act[s]:
                continue
            job = self._slot_jobs[s]
            poisoned = not (np.isfinite(host["obj"][s])
                            and np.all(np.isfinite(host["w"][s])))
            if poisoned:
                # fault isolation: THIS slot rolls back to its pre-step
                # carry (sanitized — a poisoned certificate re-enters
                # refusing, delta = inf) and the step is not recorded; the
                # other tenants' outputs are committed normally
                if job.retries < job.max_retries:
                    job.retries += 1
                    self._bump("retries")
                    time.sleep(self._retry_backoff_s * (2 ** (job.retries - 1)))
                    self._carry = self._restore_slot_carry(carry_prev, s)
                    continue
                self._evict_failed(
                    s, f"non-finite step output at lambda index {job.t} "
                       f"after {job.retries} retries")
                continue
            job.steps.append({k: v[s] for k, v in host.items()})
            self._last_kept[s] = int(host["kept"][s])
            job.t += 1
            if job.t >= len(job.lambdas):
                self._finish(s)

    def _restore_slot_carry(self, carry_prev, s: int):
        """Splice slot ``s``'s pre-step carry back in, sanitized: non-finite
        weights/bias/theta become zeros (always feasible), a non-finite
        ``delta`` becomes ``+inf`` (a *refusing* certificate — the retried
        step screens keep-all instead of trusting poison), and a non-finite
        keep flag re-enters live."""
        w, b, th, dl, lp, km = self._carry
        pw, pb, pth, pdl, plp, pkm = carry_prev
        fin = lambda a: jnp.where(jnp.isfinite(a), a, jnp.zeros_like(a))
        return (
            w.at[s].set(fin(pw[s])),
            b.at[s].set(fin(pb[s])),
            th.at[s].set(fin(pth[s])),
            dl.at[s].set(jnp.where(jnp.isfinite(pdl[s]), pdl[s],
                                   jnp.asarray(jnp.inf, dl.dtype))),
            lp.at[s].set(jnp.where(jnp.isfinite(plp[s]), plp[s],
                                   jnp.asarray(self._lam_host[s], lp.dtype))),
            km.at[s].set(jnp.where(jnp.isfinite(pkm[s]), pkm[s],
                                   jnp.ones_like(pkm[s]))),
        )

    def _evict_failed(self, slot: int, msg: str):
        """Quarantine a poisoned/overdue job: mask its slot out of the
        batch, zero the slot state (no NaN residue for the next tenant),
        evict with ``status="failed"`` — results stay 1:1 with jobs (the
        failed job's ``result`` is None, its ``error`` says why)."""
        job = self._slot_jobs[slot]
        job.status = "failed"
        job.error = msg
        job.t_done = time.perf_counter()
        job.result = None
        self._bump("jobs_failed")
        obs_metrics.histogram("serve.latency_s").observe(
            float(job.t_done - job.t_submit))
        self._tracked_done.append(job)
        self._act[slot] = False
        self._slot_jobs[slot] = None
        self._carry = tuple(c.at[slot].set(jnp.zeros_like(c[slot]))
                            for c in self._carry)

    def _assemble(self, job: PathJob) -> PathResult:
        """Build the job's PathResult from its streamed per-step outputs
        (also the resume path's way to re-materialize finished jobs)."""
        m = job.X.shape[0]
        stacked = {k: np.stack([st[k] for st in job.steps])
                   for k in ScanPathOutputs._fields}
        stacked["w"] = stacked["w"][:, :m]
        stacked["fmask"] = stacked["fmask"][:, :m]
        # mask-fallback steps report the bucket width; clamp to the true m
        stacked["cap"] = np.minimum(stacked["cap"], m)
        outs = ScanPathOutputs(**stacked)
        latency = job.t_done - job.t_submit
        with obs_trace.span("serve.result", jid=job.jid):
            r = _to_path_result(job.lambdas, outs, job.lam_max,
                                latency, job.screening,
                                self._cfg, engine="serve")
        r.extras["engine"] = "serve"
        r.extras["jid"] = job.jid
        r.extras["latency_s"] = latency
        # the shared PathTrace latency field: the job's queue-to-done wall
        # lands in total_s, same slot the host driver's summed step walls
        # use — one bookkeeping scheme across engines
        pt = r.extras["path_trace"]
        pt.meta["jid"] = job.jid
        pt.meta["latency_s"] = float(latency)
        job.result = r
        return r

    def _finish(self, slot: int):
        job = self._slot_jobs[slot]
        job.t_done = time.perf_counter()
        self._assemble(job)
        job.status = "done"
        self._bump("jobs_done")
        obs_metrics.histogram("serve.latency_s").observe(
            float(job.t_done - job.t_submit))
        self._tracked_done.append(job)
        self._act[slot] = False
        self._slot_jobs[slot] = None

    # -- snapshot / resume -------------------------------------------------

    def _snapshot(self, mgr: CheckpointManager, pending: list):
        """Checkpoint the complete serve state at the current step count.

        Arrays (device slot buffers + each job's stacked step stream +
        grids) go in the npz; everything discrete (group key, slot->jid
        map, queue order, per-job progress/status) rides the JSON manifest.
        The write is atomic (tmp + rename), so a crash mid-snapshot leaves
        the previous one valid.
        """
        now = time.perf_counter()
        flat = {
            "X": self._X, "y": self._y, "sm": self._sm,
            "inv_L": self._inv_L, "lam_host": self._lam_host,
            "last_kept": self._last_kept, "act": self._act,
        }
        for i, a in enumerate(self._statics):
            flat[f"statics{i}"] = a
        for i, a in enumerate(self._carry):
            flat[f"carry{i}"] = a
        jobs_meta = {}
        tracked = [j for j in self._slot_jobs if j is not None]
        tracked += list(pending) + list(self._tracked_done)
        for job in tracked:
            jid = int(job.jid)
            jobs_meta[str(jid)] = {
                "t": int(job.t), "retries": int(job.retries),
                "status": job.status, "error": job.error,
                "lam_max": float(job.lam_max),
                "elapsed": float(now - job.t_submit),
                "started": float(now - job.t_start) if job.t_start else -1.0,
                "n_steps": len(job.steps),
            }
            if job.lambdas is not None:
                flat[f"job{jid}_lambdas"] = np.asarray(job.lambdas)
            for f in ScanPathOutputs._fields:
                if job.steps:
                    flat[f"job{jid}_{f}"] = np.stack(
                        [np.asarray(st[f]) for st in job.steps])
        m_b, n_b, rule_stack, dynamic = self._group
        extra = {
            "group": [int(m_b), int(n_b), list(rule_stack), bool(dynamic)],
            "slots": [int(j.jid) if j is not None else -1
                      for j in self._slot_jobs],
            "pending": [int(j.jid) for j in pending],
            "jobs": jobs_meta,
            "stats": {k: int(v) for k, v in self.stats.items()},
        }
        mgr.save(self.stats["steps"], flat, extra=extra)

    def _restore_serve(self, mgr: CheckpointManager,
                       jobs: list) -> Optional[list]:
        """Resume from the latest snapshot: rebuild device slot state,
        splice each job's recorded progress back (matched by ``jid``), and
        return the restored pending queue — or None when there is no valid
        snapshot (fresh serve)."""
        step = mgr.latest()
        if step is None:
            return None
        flat, manifest = mgr.restore_raw(step)
        ex = manifest["extra"]
        by_jid = {int(j.jid): j for j in jobs}
        g = ex["group"]
        self._alloc_group((int(g[0]), int(g[1]), tuple(g[2]), bool(g[3])))
        self._X = jnp.asarray(flat["X"])
        self._y = jnp.asarray(flat["y"])
        self._sm = jnp.asarray(flat["sm"])
        self._inv_L = jnp.asarray(flat["inv_L"])
        self._statics = tuple(jnp.asarray(flat[f"statics{i}"])
                              for i in range(5))
        self._carry = tuple(jnp.asarray(flat[f"carry{i}"])
                            for i in range(6))
        self._lam_host = np.asarray(flat["lam_host"], np.float64).copy()
        self._last_kept = np.asarray(flat["last_kept"], np.int64).copy()
        self._act = np.asarray(flat["act"], bool).copy()
        now = time.perf_counter()
        self._tracked_done = []
        for jid_s, jm in ex["jobs"].items():
            job = by_jid.get(int(jid_s))
            if job is None:
                raise ValueError(
                    f"snapshot references job {jid_s} missing from the "
                    f"resubmitted job list")
            job.t = int(jm["t"])
            job.retries = int(jm["retries"])
            job.status = jm["status"]
            job.error = jm["error"]
            job.lam_max = float(jm["lam_max"])
            job.t_submit = now - float(jm["elapsed"])
            job.t_start = (now - float(jm["started"])
                           if jm["started"] >= 0 else 0.0)
            key = f"job{int(jid_s)}_lambdas"
            if key in flat:
                job.lambdas = np.asarray(flat[key])
            n_steps = int(jm["n_steps"])
            if n_steps:
                stacks = {f: flat[f"job{int(jid_s)}_{f}"]
                          for f in ScanPathOutputs._fields}
                job.steps = [{f: stacks[f][k] for f in stacks}
                             for k in range(n_steps)]
            if job.status == "done":
                job.t_done = job.t_submit + float(jm["elapsed"])
                self._assemble(job)
                self._tracked_done.append(job)
                self._bump("jobs_done")
            elif job.status == "failed":
                job.t_done = job.t_submit + float(jm["elapsed"])
                self._tracked_done.append(job)
                self._bump("jobs_failed")
        self._slot_jobs = [by_jid[j] if j >= 0 else None
                           for j in ex["slots"]]
        # restore is an assignment in the legacy dict; mirror it into the
        # monotone registry counter as the delta so both stay equal
        restored = int(ex["stats"].get("steps", manifest["step"]))
        self._bump("steps", restored - self.stats["steps"])
        return [by_jid[j] for j in ex["pending"]]

    # -- the serve loop ----------------------------------------------------

    def serve(self, jobs: list[PathJob], log=None,
              snapshot_dir=None, snapshot_every: int = 0,
              ) -> list[Optional[PathResult]]:
        """Drain a job queue; returns results in submission order (a failed
        job's entry is None — see its ``.error``).

        Continuous batching: empty slots refill from the queue (same bucket
        group) before every step, so ragged grid lengths keep the device
        program saturated instead of waiting on the longest path.

        ``snapshot_dir`` enables crash recovery: serve state (device slot
        buffers, per-job step streams, queue order, progress) is
        checkpointed there every ``snapshot_every`` steps (atomically, via
        :class:`CheckpointManager`). Calling ``serve`` again with the same
        ``jobs`` list (matched by ``jid``) and the same ``snapshot_dir``
        resumes from the latest snapshot instead of starting over, and the
        resumed run's results equal an uninterrupted run's.
        """
        if log is None:
            log = _LOG.info
        pending = list(jobs)
        t0 = time.perf_counter()
        for j in pending:
            j.t_submit = t0
        mgr = (CheckpointManager(snapshot_dir, keep=2)
               if snapshot_dir is not None else None)
        resumed = self._restore_serve(mgr, jobs) if mgr is not None else None
        if resumed is not None:
            pending = resumed
        else:
            self._tracked_done = []
        while pending or self._act.any():
            if not self._act.any():
                nxt_group = pending[0].group_key()
                if self._group != nxt_group:
                    self._alloc_group(nxt_group)
            with obs_trace.span("serve.refill", pending=len(pending)):
                for s in range(self.slots):
                    if not self._act[s]:
                        nxt = next((j for j in pending
                                    if j.group_key() == self._group), None)
                        if nxt is None:
                            break
                        pending.remove(nxt)
                        self._insert(s, nxt)
            with obs_trace.span("serve.step", step=self.stats["steps"],
                                occupied=int(self._act.sum())):
                self.step()
            if (mgr is not None and snapshot_every
                    and self.stats["steps"] % int(snapshot_every) == 0):
                with obs_trace.span("serve.checkpoint",
                                    step=self.stats["steps"]):
                    self._snapshot(mgr, pending)
            if self._step_hook is not None:
                self._step_hook(self.stats["steps"])
        wall = time.perf_counter() - t0
        lat = np.array([j.t_done - j.t_submit for j in jobs])
        occ = (self.stats["occupied_slots"]
               / max(1, self.stats["steps"] * self.slots))
        self.last_serve = dict(
            jobs=len(jobs), wall_s=float(wall),
            jobs_per_s=len(jobs) / wall, steps=self.stats["steps"],
            slot_occupancy=float(occ),
            latency_p50_s=float(np.percentile(lat, 50)),
            latency_p95_s=float(np.percentile(lat, 95)),
            **self.cache_stats(),
        )
        obs_metrics.gauge("serve.slot_occupancy").set(float(occ))
        log(f"[serve] {len(jobs)} jobs in {wall:.2f}s "
            f"({self.last_serve['jobs_per_s']:.2f} jobs/s), "
            f"occupancy={occ:.2f}, cache={self.cache_stats()}")
        return [j.result for j in jobs]


def demo_jobs(n_jobs: int = 8, m: int = 300, n: int = 120,
              seed: int = 0, ragged: bool = True) -> list[PathJob]:
    """A mixed-grid job workload over independent synthetic problems."""
    from repro.data import make_sparse_classification

    rng = np.random.default_rng(seed)
    jobs = []
    for i in range(n_jobs):
        ds = make_sparse_classification(m=m, n=n, k_active=10, seed=seed + i)
        T = int(rng.integers(4, 10)) if ragged else 8
        jobs.append(PathJob(jid=i, X=np.asarray(ds.X), y=np.asarray(ds.y),
                            n_lambdas=T,
                            lam_min_ratio=float(rng.uniform(0.1, 0.3))))
    return jobs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--m", type=int, default=300)
    ap.add_argument("--n", type=int, default=120)
    ap.add_argument("--reduce", choices=("mask", "compact"),
                    default="compact")
    ap.add_argument("--tol", type=float, default=1e-9)
    args = ap.parse_args()

    log_setup()
    enable_compile_cache()
    server = PathServer(slots=args.slots, reduce=args.reduce, tol=args.tol)
    jobs = demo_jobs(args.jobs, m=args.m, n=args.n)
    results = server.serve(jobs)
    for r in results:
        _LOG.info(
            "job %d: T=%d final nnz=%d obj=%.5f latency=%.2fs",
            r.extras["jid"], len(r.lambdas), int(r.active[-1]),
            float(r.objectives[-1]), r.extras["latency_s"])
    Path("artifacts").mkdir(exist_ok=True)
    Path("artifacts/svm_serve.json").write_text(
        json.dumps(server.last_serve, indent=2))
    Path("artifacts/svm_serve_metrics.json").write_text(
        json.dumps(server.metrics(), indent=2, default=str))


if __name__ == "__main__":
    main()
