"""Distributed sparse-SVM path trainer — the paper's workload as a launcher.

Runs the sequential-screening regularization path with the 2-D sharded
(model x data) screen + FISTA from core/distributed.py, with checkpointing of
the path state ((lambda_k, w, b, theta) per step) so a preempted path job
resumes at the last completed lambda.

Screening is configured through the rule registry (core/rules):
``--rules feature_vi|sample_vi|composite|dvi|none``. The feature rule
dispatches to the sharded bound sweep (``screen_sharded`` — same math,
psum-reduced, delta-inflated for the sequentially-solved anchor); sample
rules run their margin test on the replicated sample axis and mask the loss
inside ``fista_sharded`` (static shapes, shard-friendly), with the rule's
KKT verification loop re-admitting violators before a step commits.
``--dynamic`` additionally re-screens *inside* the sharded FISTA loop every
``--screen-every`` iterations from the gap-certified region at the current
iterate, ANDing into a live "model"-sharded feature mask (per-segment kept
counts land in the results JSON).

``--engine scan`` swaps the host loop for the on-device path engine
(``core/path_scan.py``): the whole path runs as ONE jitted program — as a
single ``shard_map``'d program on the (model x data) mesh when the mesh has
more than one device, locally otherwise. ``--reduce compact`` (single-device
scan) additionally gathers each step's certified active set into a
fixed-capacity buffer so solver FLOPs track what screening keeps. The scan
engine is feature-rule-only and runs start-to-finish in one dispatch, so
``--rules``/``--dynamic`` and per-step checkpoint/resume stay host-engine
features.

``--serve`` switches the launcher into multi-tenant mode: a synthetic
mixed-grid job queue drains through ``launch/path_server.py`` (continuous
batching of the batched scan step, ``--reduce`` selecting mask vs
shared-cap compact solves) and the throughput/cache stats land in
``artifacts/svm_serve.json``.

CPU smoke: PYTHONPATH=src python -m repro.launch.train_svm --m 2000 --n 400
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.obs import trace as obs_trace
from repro.obs.trace import span
from repro.obs.log import get_logger, setup as log_setup

_LOG = get_logger("launch.train_svm")

from repro.checkpoint import CheckpointManager
from repro.core import (
    default_lambda_grid,
    lambda_max,
    lipschitz_estimate,
    theta_at_lambda_max,
)
from repro.core.distributed import (
    fista_sharded,
    sample_surplus_sharded,
    screen_sharded,
    svm_mesh,
)
from repro.core.dual import safe_theta_and_delta
from repro.core.rules import (
    AXIS_FEATURES,
    AXIS_SAMPLES,
    ConvexRegion,
    FeatureVIRule,
    SampleVIRule,
    make_rules,
)
from repro.core.rules.base import dynamic_tau, solve_with_verification
from repro.data import load_libsvm, make_sparse_classification


def run_path_scan(
    X: np.ndarray, y: np.ndarray,
    n_lambdas: int = 10, lam_min_ratio: float = 0.1,
    model: int = 1, data: int = 1,
    tol: float = 1e-9, max_iters: int = 4000,
    reduce: str = "mask",
    rules: str = "feature_vi",
    dynamic: bool = False,
    screen_every: int = 50,
    exact_lipschitz: bool = False,
    log=None,
):
    """The launcher's scan-engine lane: one program, no per-step host loop.

    Multi-device meshes run ``svm_path_scan_sharded`` (mask reduction — the
    feature axis is already divided by sharding — and no in-solver dynamic
    re-screen yet); a single-device mesh runs ``svm_path_scan`` and honors
    ``--reduce compact`` and ``--dynamic/--screen-every``. Unsupported flag
    combinations raise rather than silently running a different experiment.
    """
    from repro.core import svm_path_scan, svm_path_scan_sharded

    if log is None:
        log = _LOG.info
    # lowerability of the rule spec is validated by the engines at dispatch
    # (rules/programs.resolve_programs): any a-priori-safe feature-rule
    # stack (feature_vi / edpp / dvi / auto / lists) runs in the jitted
    # step; sample rules and sifs raise with a pointer to --engine host
    screening = rules != "none"
    if model * data > 1:
        if reduce == "compact":
            raise ValueError(
                "--reduce compact needs the single-device scan engine "
                "(compaction indexes global feature rows); on a mesh the "
                "feature axis is already divided by sharding — use "
                "--reduce mask"
            )
        if dynamic:
            raise ValueError(
                "--dynamic is not plumbed through the sharded scan engine "
                "yet; use --engine host or a single-device mesh"
            )
        mesh = svm_mesh(model=model, data=data)
        r = svm_path_scan_sharded(mesh, X, y, n_lambdas=n_lambdas,
                                  lam_min_ratio=lam_min_ratio, tol=tol,
                                  max_iters=max_iters, screening=screening,
                                  exact_lipschitz=exact_lipschitz,
                                  rules=rules)
    else:
        r = svm_path_scan(X, y, n_lambdas=n_lambdas,
                          lam_min_ratio=lam_min_ratio, tol=tol,
                          max_iters=max_iters, reduce=reduce,
                          screening=screening, dynamic=dynamic,
                          screen_every=screen_every,
                          exact_lipschitz=exact_lipschitz,
                          rules=rules)
    m = X.shape[0]
    results = []
    for k in range(len(r.lambdas)):
        row = {"lam": float(r.lambdas[k]), "kept": int(r.kept[k]),
               "nnz": int(r.active[k]), "obj": float(r.objectives[k]),
               "iters": int(r.solver_iters[k]),
               "cap": int(r.extras["caps"][k]),
               "resurrected": int(r.extras["resurrected"][k])}
        results.append(row)
        log(f"[svm] k={k} lam={row['lam']:.4f} kept={row['kept']}/{m} "
            f"cap={row['cap']} nnz={row['nnz']} obj={row['obj']:.5f}")
    log(f"[svm] engine={r.extras['engine']} reduce={reduce} "
        f"total={r.extras['total_seconds']:.2f}s (single dispatch, "
        "per-step walls not observable)")
    return results


def run_path(
    X: np.ndarray, y: np.ndarray,
    n_lambdas: int = 10, lam_min_ratio: float = 0.1,
    model: int = 1, data: int = 1,
    tol: float = 1e-9, max_iters: int = 4000,
    ckpt_dir: str = "artifacts/svm_ckpt", log=None,
    rules: str = "feature_vi",
    shrink_factor: float = 1.5,
    max_verify_rounds: int = 3,
    dynamic: bool = False,
    screen_every: int = 50,
    exact_lipschitz: bool = False,
):
    if log is None:
        log = _LOG.info
    mesh = svm_mesh(model=model, data=data)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    m, n = Xj.shape
    X_np, y_np = np.asarray(X), np.asarray(y)

    # one Lipschitz estimate serves the whole path: every per-step solve is
    # a masked reduction of X, whose sigma_max never exceeds the full
    # matrix's (see solver.lipschitz_estimate) — saves the 30-iteration
    # distributed power sweep per solve and per verification round.
    L_path = None if exact_lipschitz else lipschitz_estimate(Xj)

    rule_list = make_rules(None if rules in (None, "none") else rules)
    feature_rules = [r for r in rule_list if r.axis == AXIS_FEATURES]
    sample_rules = [r for r in rule_list if r.axis == AXIS_SAMPLES]
    # the stock rules dispatch to their sharded psum sweeps (same bounds /
    # surpluses, mesh-parallel): feature_vi -> screen_sharded, sample_vi ->
    # sample_surplus_sharded; other rules go through their generic
    # bounds/keep. Only the generic-path rules need their prepare() caches
    # (the sharded sample sweep keeps its secant history on the rule object).
    sharded_feature = [r for r in feature_rules if type(r) is FeatureVIRule]
    generic_feature = [r for r in feature_rules if type(r) is not FeatureVIRule]
    sharded_sample = [r for r in sample_rules if type(r) is SampleVIRule]
    generic_sample = [r for r in sample_rules if type(r) is not SampleVIRule]
    for rule in (*generic_feature, *sample_rules):
        rule.prepare(Xj, yj)

    lmax = float(lambda_max(Xj, yj))
    lambdas = default_lambda_grid(lmax, n_lambdas, lam_min_ratio)
    mgr = CheckpointManager(ckpt_dir, keep=2)

    state = {
        "w": jnp.zeros((m,), jnp.float32),
        "b": jnp.asarray(float(jnp.mean(yj)), jnp.float32),
        "theta": theta_at_lambda_max(yj, jnp.asarray(lmax)),
        "delta": jnp.asarray(0.0, jnp.float32),
        "dw": jnp.asarray(jnp.inf, jnp.float32),
        "db": jnp.asarray(jnp.inf, jnp.float32),
        "k": jnp.asarray(0, jnp.int32),
    }
    start_k = 1
    latest = mgr.latest()
    if latest is not None:
        # strict=False: checkpoints written before the dw/db trust-region
        # fields existed restore with those fields at their defaults
        state, manifest = mgr.restore(latest, state, strict=False)
        start_k = int(manifest["extra"]["next_k"])
        log(f"[svm] resumed path at lambda index {start_k}")

    results = []
    for k in range(start_k, len(lambdas)):
        lam1, lam2 = float(lambdas[k - 1]), float(lambdas[k])
        with span("path.step", step=k, lam=lam2) as sp:
            t0 = time.perf_counter()

            region = ConvexRegion.build(
                yj, lam1, lam2, state["theta"], delta=state["delta"],
                w1=state["w"], b1=float(state["b"]),
                dw=float(state["dw"]), db=float(state["db"]),
            )
            keep = jnp.ones((m,), bool)
            for rule in sharded_feature:
                # state["delta"] bounds ||theta - theta*(lam1)|| for the
                # sequentially-solved anchor; without it the sharded screen
                # would assume theta exact and could unsafely reject features
                k_mask, _ = screen_sharded(mesh, Xj, yj, lam1, lam2,
                                           state["theta"], tau=rule.tau,
                                           delta=state["delta"])
                keep = keep & k_mask
            for rule in generic_feature:
                keep = keep & jnp.asarray(
                    rule.keep(rule.bounds(Xj, yj, region)))
            s_mask = np.ones((n,), dtype=bool)
            for rule in sharded_sample:
                # the mesh-parallel margin sweep (ROADMAP: queued since PR 4):
                # same two feature-axis reductions, psum over "model", and the
                # rule's own slack arithmetic — bitwise the local oracle on
                # meshes that keep the feature axis whole. The secant history
                # lives on the rule object, exactly as in the local path.
                surplus, u1 = sample_surplus_sharded(
                    mesh, Xj, yj, state["w"], float(state["b"]),
                    dw=float(state["dw"]), db=float(state["db"]),
                    u_prev=rule._u_prev, shrink_factor=rule.shrink_factor,
                    margin_floor=rule.margin_floor,
                )
                rule._u_prev = u1
                s_mask &= np.asarray(surplus < 0.0)
            for rule in generic_sample:
                s_mask &= np.asarray(rule.keep(rule.bounds(Xj, yj, region)))

            kept = int(jnp.sum(keep))
            # mask-mode reduction keeps static shapes across the sharded solve
            Xr = Xj * keep[:, None].astype(Xj.dtype)
            warm = {"w": state["w"] * keep, "b": state["b"]}

            def solve(mask):
                # the dynamic segmented solve keeps tightening the feature mask
                # in-loop, seeded from the between-lambda sequential screen
                r = fista_sharded(
                    mesh, Xr, yj, lam2, max_iters=max_iters, tol=tol,
                    w0=warm["w"], b0=warm["b"],
                    sample_mask=jnp.asarray(mask, jnp.float32),
                    feature_mask=keep.astype(jnp.float32),
                    screen_every=screen_every if dynamic else None,
                    tau=dynamic_tau(feature_rules),
                    L=L_path,
                )
                warm["w"], warm["b"] = r.w, r.b
                return r, np.asarray(r.w, np.float64), float(r.b)

            res, _, _, rounds = solve_with_verification(
                solve, sample_rules, X_np, y_np, s_mask,
                max_rounds=max_verify_rounds,
            )

            dw_obs = float(jnp.linalg.norm(res.w - state["w"]))
            db_obs = abs(float(res.b) - float(state["b"]))
            theta, delta = safe_theta_and_delta(Xj, yj, res.w, res.b,
                                                jnp.asarray(lam2))
            state = {"w": res.w, "b": res.b, "theta": theta, "delta": delta,
                     "dw": jnp.asarray(shrink_factor * dw_obs, jnp.float32),
                     "db": jnp.asarray(shrink_factor * db_obs, jnp.float32),
                     "k": jnp.asarray(k, jnp.int32)}
            dt = time.perf_counter() - t0
            sp.set(kept=kept, iters=int(res.n_iters))
        nnz = int(jnp.sum(jnp.abs(res.w) > 1e-8))
        kept_n = int(s_mask.sum())
        row = {"lam": lam2, "kept": kept, "kept_samples": kept_n,
               "nnz": nnz, "obj": float(res.obj),
               "iters": int(res.n_iters), "verify_rounds": rounds,
               "wall_s": dt}
        dyn_note = ""
        if hasattr(res, "kept_per_segment"):
            n_seg = int(res.n_segments)
            segs = [int(v) for v in np.asarray(res.kept_per_segment)[:n_seg]]
            row["dynamic_kept_per_segment"] = segs
            row["kept_final"] = int(np.asarray(res.feature_mask).sum())
            dyn_note = f" dyn={segs}"
        results.append(row)
        log(f"[svm] k={k} lam={lam2:.4f} kept={kept}/{m} "
            f"samples={kept_n}/{n} nnz={nnz} obj={float(res.obj):.5f} "
            f"({dt:.2f}s){dyn_note}")
        mgr.save(k, state, extra={"next_k": k + 1, "lambdas": list(map(float, lambdas))})
    return results


def run_path_chunked(
    X, y, csr=None,
    n_lambdas: int = 10, lam_min_ratio: float = 0.1,
    tol: float = 1e-9, max_iters: int = 4000,
    rules: str = "feature_vi",
    storage: str = "chunked", chunk_m: int = 512,
    exact_lipschitz: bool = False,
    chunk_skip: bool = True,
    dynamic: bool = False,
    screen_every: int = 50,
    libsvm_path=None,
    store_dir=None,
    log=None,
):
    """The launcher's out-of-core lane: stream the screened path over
    ``repro.sparse.FeatureChunked`` storage (``--storage chunked|csr``).

    ``csr`` (a ``repro.data.CsrData``, e.g. from a sparse synthetic design
    or the libsvm loader) backs ``--storage csr``; low-density chunks sweep
    as BCOO so screening FLOPs track nnz. ``store_dir`` (with a libsvm
    input) keeps the chunks disk-resident: the file is converted once into
    an mmap-backed chunk store (``FeatureChunked.from_libsvm_cached``) and
    subsequent runs open the store without re-parsing — host RAM holds no
    copy of X either. Single-host by construction — the whole point is
    that only one chunk (plus the screened active set) ever sits on the
    device; ``chunk_skip`` additionally skips the *transfer* of chunks the
    stale-anchor cache certifies dead (see ``PathDriver``).
    """
    from repro.core import PathDriver
    from repro.sparse import FeatureChunked

    if log is None:
        log = _LOG.info
    # program-backed feature stacks stream (feature_vi / edpp / dvi /
    # auto); sample rules (sample_vi / composite / sifs) ride the
    # transposed sweep + carried-margin verification; the driver lane
    # validates the spec itself
    rule_spec = [] if rules in (None, "none") else rules
    if storage == "mmap" or store_dir is not None:
        if libsvm_path is not None:
            fc, y = FeatureChunked.from_libsvm_cached(
                libsvm_path, store_dir=store_dir, chunk_m=chunk_m)
        elif store_dir is not None:
            # open an existing store directly: a missing directory raises
            # StoreMissingError, checksum/size damage StoreCorruptError —
            # both reach the CLI as a typed message + nonzero exit
            fc = FeatureChunked.from_store(store_dir, chunk_m=chunk_m)
            fc.verify()
            if fc.labels is None:
                raise ValueError(
                    f"store {store_dir} has no labels (y.bin); rebuild it "
                    "from the source text with --libsvm FILE")
            y = fc.labels
        else:
            raise ValueError("--storage mmap needs --libsvm FILE (to build "
                             "the store) or --store-dir DIR (to open an "
                             "existing one)")
    elif storage == "csr":
        if csr is None:
            raise ValueError(
                "--storage csr needs a CSR-backed dataset: generate with "
                "--density < 1 or load one with --libsvm"
            )
        fc = FeatureChunked.from_csr(csr, chunk_m=chunk_m)
    else:
        fc = FeatureChunked.from_dense(X, chunk_m=chunk_m)
    driver = PathDriver(rules=rule_spec, tol=tol, max_iters=max_iters,
                        exact_lipschitz=exact_lipschitz,
                        chunk_skip=chunk_skip, dynamic=dynamic,
                        screen_every=screen_every)
    r = driver.run(fc, y, n_lambdas=n_lambdas, lam_min_ratio=lam_min_ratio)
    m, n = fc.shape
    results = []
    for k in range(len(r.lambdas)):
        row = {"lam": float(r.lambdas[k]), "kept": int(r.kept[k]),
               "kept_samples": int(r.kept_samples[k]),
               "live_chunks": int(r.extras["live_chunks"][k]),
               "nnz": int(r.active[k]), "obj": float(r.objectives[k]),
               "iters": int(r.solver_iters[k]),
               "wall_s": float(r.wall_times[k])}
        results.append(row)
        log(f"[svm] k={k} lam={row['lam']:.4f} kept={row['kept']}/{m} "
            f"samples={row['kept_samples']}/{n} "
            f"chunks={row['live_chunks']}/{r.extras['n_chunks']} "
            f"nnz={row['nnz']} obj={row['obj']:.5f} ({row['wall_s']:.2f}s)")
    st = r.extras["stream_stats"]
    log(f"[svm] storage={storage} chunks={r.extras['n_chunks']} "
        f"chunk_m={chunk_m} max_device_rows={st['max_put_rows']} "
        f"transfers={st['puts']} bcoo_transfers={st['bcoo_puts']} "
        f"streamed={st['chunks_streamed']} skipped={st['chunks_skipped']} "
        f"bytes_put={st['bytes_put']}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=2000)
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--n-lambdas", type=int, default=8)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--density", type=float, default=1.0,
                    help="synthetic X density; < 1 also builds a true CSR "
                         "representation (feeds --storage csr)")
    ap.add_argument("--libsvm", default=None, metavar="FILE",
                    help="load a libsvm/svmlight text file instead of "
                         "generating synthetic data")
    ap.add_argument("--storage", choices=("dense", "chunked", "csr", "mmap"),
                    default="dense",
                    help="dense: in-core (m, n) device matrix; chunked: "
                         "host-resident feature chunks streamed to device "
                         "(out-of-core); csr: chunked CSR, low-density "
                         "chunks swept as BCOO; mmap: disk-resident chunk "
                         "store built once from --libsvm (nothing in host "
                         "RAM either)")
    ap.add_argument("--chunk-m", type=int, default=512,
                    help="feature rows per chunk for --storage "
                         "chunked|csr|mmap")
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="mmap chunk-store directory for --storage mmap "
                         "(default: <libsvm file>.store)")
    ap.add_argument("--no-chunk-skip", dest="chunk_skip",
                    action="store_false",
                    help="chunked storage: stream every chunk every step "
                         "instead of skipping chunks certified dead by the "
                         "stale-anchor cache (the full-stream baseline)")
    ap.add_argument("--rules", default="feature_vi",
                    help="screening rules: feature_vi|sample_vi|composite|"
                         "dvi|edpp|sifs|auto|none (comma-separated for a "
                         "custom mix; the scan engine takes a-priori-safe "
                         "feature-rule stacks only, chunked storage adds "
                         "verified sample rules via the transposed sweep)")
    ap.add_argument("--engine", choices=("host", "scan"), default="host",
                    help="host: per-step sharded loop with checkpointing; "
                         "scan: the whole path as one (shard_map'd) XLA "
                         "program (a-priori-safe feature-rule stacks only)")
    ap.add_argument("--reduce", choices=("mask", "compact"), default="mask",
                    help="scan engine: mask-mode solve vs on-device "
                         "active-set compaction (single-device mesh only)")
    ap.add_argument("--dynamic", action="store_true",
                    help="re-screen inside the sharded FISTA loop every "
                         "--screen-every iterations (gap-certified)")
    ap.add_argument("--screen-every", type=int, default=50)
    ap.add_argument("--exact-lipschitz", action="store_true",
                    help="re-estimate L per solve instead of reusing the "
                         "full-X upper bound computed once per path")
    ap.add_argument("--ckpt-dir", default="artifacts/svm_ckpt")
    ap.add_argument("--serve", action="store_true",
                    help="multi-tenant mode: drain a synthetic job mix "
                         "through launch/path_server.py (continuous "
                         "batching of the batched scan step) instead of "
                         "solving one path")
    ap.add_argument("--serve-jobs", type=int, default=8)
    ap.add_argument("--serve-slots", type=int, default=4)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record the program's spans on the host and "
                         "export them as Chrome trace-event JSON here "
                         "(open in Perfetto / chrome://tracing; no device "
                         "ops); equivalent to REPRO_TRACE=1 plus an export")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the path into "
                         "DIR (view with TensorBoard / Perfetto): the "
                         "program's host spans (svm_path, "
                         "svm_path.dispatch, ...) and its device ops under "
                         "their named scopes (svm_path/prologue, "
                         "svm_path/certify/feasibility, ...) on one "
                         "timeline")
    args = ap.parse_args()

    log_setup()
    enable_compile_cache()
    _obs_begin(args)
    try:
        _run(args, ap)
    finally:
        _obs_end(args)


def _obs_begin(args):
    """Arm the observability capture selected on the command line: the obs
    span recorder (``--trace``) and/or the jax device profiler
    (``--profile``), both spanning the whole path dispatch."""
    if args.trace:
        obs_trace.enable()
    if args.profile:
        jax.profiler.start_trace(args.profile)


def _obs_end(args):
    if args.profile:
        jax.profiler.stop_trace()
        _LOG.info("profiler trace captured to %s", args.profile)
    if args.trace:
        path = obs_trace.export_chrome(args.trace)
        _LOG.info("chrome trace written to %s (load in Perfetto)", path)


def _run(args, ap):
    if args.serve:
        from repro.launch.path_server import PathServer, demo_jobs

        if args.engine != ap.get_default("engine") or args.storage != "dense":
            raise SystemExit(
                "--serve runs the batched scan step through the path "
                "server; --engine/--storage do not apply"
            )
        server = PathServer(slots=args.serve_slots, reduce=args.reduce)
        jobs = demo_jobs(args.serve_jobs, m=args.m, n=args.n)
        server.serve(jobs)
        Path("artifacts").mkdir(exist_ok=True)
        Path("artifacts/svm_serve.json").write_text(
            json.dumps(server.last_serve, indent=2))
        Path("artifacts/svm_serve_metrics.json").write_text(
            json.dumps(server.metrics(), indent=2, default=str))
        return

    rules = args.rules if "," not in args.rules else args.rules.split(",")
    if args.storage == "mmap":
        # the mmap store is built straight from the file (or opened from
        # --store-dir) by the chunked lane — never materialize X in host
        # RAM here
        from repro.data import SvmDataset

        ds = SvmDataset(X=None, y=None, w_true=None, csr=None)
    elif args.libsvm:
        ds = load_libsvm(args.libsvm)
    else:
        ds = make_sparse_classification(m=args.m, n=args.n, seed=0,
                                        density=args.density)
    if args.engine == "host" and args.reduce != "mask":
        raise SystemExit(
            f"--reduce {args.reduce} is a scan-engine option; the host "
            "engine reduces via its rule drivers (gather/mask). Add "
            "--engine scan."
        )
    if args.engine == "scan" and args.ckpt_dir != ap.get_default("ckpt_dir"):
        raise SystemExit(
            "--ckpt-dir has no effect with --engine scan: the whole path is "
            "one dispatch, so there is no per-step state to checkpoint or "
            "resume. Use --engine host for checkpointed paths."
        )
    if args.storage != "dense":
        if args.engine == "scan":
            raise SystemExit(
                "--storage chunked|csr runs on the host engine (the scan "
                "engine jit-compiles over an in-core X); drop --engine scan"
            )
        if args.model * args.data > 1:
            raise SystemExit(
                "--storage chunked|csr|mmap is single-host streaming (one "
                "chunk on one device); use --storage dense for sharded "
                "meshes"
            )
        from repro.sparse import StoreError

        try:
            results = run_path_chunked(
                ds.X, ds.y, csr=ds.csr, n_lambdas=args.n_lambdas,
                rules=args.rules, storage=args.storage, chunk_m=args.chunk_m,
                exact_lipschitz=args.exact_lipschitz,
                chunk_skip=args.chunk_skip, dynamic=args.dynamic,
                screen_every=args.screen_every,
                libsvm_path=args.libsvm, store_dir=args.store_dir)
        except StoreError as e:
            # typed storage failure (missing store, checksum mismatch,
            # exhausted read retries) — a clean message and a nonzero
            # exit, not a traceback
            _LOG.error("%s: %s", type(e).__name__, e)
            raise SystemExit(2)
        Path("artifacts").mkdir(exist_ok=True)
        Path("artifacts/svm_path.json").write_text(json.dumps(results, indent=2))
        return
    if args.engine == "scan":
        results = run_path_scan(ds.X, ds.y, n_lambdas=args.n_lambdas,
                                model=args.model, data=args.data,
                                reduce=args.reduce, rules=args.rules,
                                dynamic=args.dynamic,
                                screen_every=args.screen_every,
                                exact_lipschitz=args.exact_lipschitz)
    else:
        results = run_path(ds.X, ds.y, n_lambdas=args.n_lambdas,
                           model=args.model, data=args.data,
                           ckpt_dir=args.ckpt_dir, rules=rules,
                           dynamic=args.dynamic,
                           screen_every=args.screen_every,
                           exact_lipschitz=args.exact_lipschitz)
    Path("artifacts").mkdir(exist_ok=True)
    Path("artifacts/svm_path.json").write_text(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
