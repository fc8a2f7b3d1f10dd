#!/usr/bin/env python3
"""Smoke run of the screened lambda path on a TPU, at rcv1 width.

    python chip_smoke.py             # one chip: the main path, end to end
    python chip_smoke.py --chips 4   # four chips: the sharded scan only

Data is generated from ``--seed`` in the shape of LIBSVM's ``rcv1.binary``
(47,236 features x 20,242 samples, about 0.16% nonzero, unit-norm tf-idf
style samples, heavy-tailed feature frequencies); nothing is downloaded.
Dense float32 X is 3.8 GB on the chip.

One chip, on 8 lambdas from ``lambda_max`` down to ``0.1 * lambda_max``:

1. ``svm_path(engine="scan", reduce="compact")`` with the default kernel
   policy; its compiled program must contain the Pallas kernels
   (``tpu_custom_call``);
2. the same grid with screening off, the reference: objectives agree, and
   no feature a screened step discarded is nonzero in the reference;
3. ``svm_path(engine="host")`` (the ``PathDriver``): objectives agree with
   the scan;
4. ``PathServer(slots=2).serve`` on 4 jobs of 16,384 x 4,096: every job
   finishes and agrees with its own ``svm_path``.

Four chips: ``svm_path_scan_sharded`` on ``svm_mesh(1, 4)`` and
``svm_mesh(2, 2)``, against the one-chip XLA scan of the same problem.

Every phase fails the run on a nonzero ``health`` word. Per-phase lines go
to stdout; the last line is ``{"ok": true, "device": {...}}``. Without a
TPU, or with Pallas interpret mode or the kernels turned off through the
environment, the script exits nonzero and prints no result. It runs in one
process and starts no other. The full record of a run goes to
``artifacts/chip_smoke_<chips>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time
from pathlib import Path

import numpy as np

RCV1 = dict(m=47_236, n=20_242, density=0.0016)  # LIBSVM rcv1.binary
SERVER_JOB = dict(m=16_384, n=4_096, density=0.0016)
GRID = dict(n_lambdas=8, lam_min_ratio=0.1)
SOLVE = dict(tol=1e-9, max_iters=20_000)
REL_TOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def say(*parts):
    print(*parts, flush=True)


#: seconds JAX spent tracing, lowering and compiling, summed from its
#: monitoring events; each phase reports its own share
COMPILE_S = [0.0]


def _count_compile(event: str, secs: float, **_):
    if event.startswith("/jax/core/compile/"):
        COMPILE_S[0] += secs


def host_peak_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6  # KiB


def rcv1_like(seed: int, m: int, n: int, density: float):
    """Seeded text-like binary problem, built in float32 on the host.

    Feature frequencies are Zipf-like (a few common terms, a long tail),
    values are positive and each sample is scaled to unit norm, as in
    tf-idf text data; labels come from a sparse direction over common
    features plus noise, split at the median. Returns ``(X, y)``, X of
    shape (m, n) float32 (features x samples).
    """
    rng = np.random.default_rng(seed)
    nnz = int(round(density * m * n))
    p = 1.0 / (np.arange(m) + 10.0) ** 1.1
    rows = rng.choice(m, size=nnz, p=p / p.sum())
    cols = rng.integers(0, n, size=nnz)
    flat = np.unique(rows.astype(np.int64) * n + cols)
    rows, cols = flat // n, flat % n
    vals = (rng.exponential(1.0, flat.size) + 0.1).astype(np.float32)
    norms = np.sqrt(np.bincount(cols, weights=vals.astype(np.float64) ** 2,
                                minlength=n))
    vals /= np.maximum(norms, 1e-12)[cols].astype(np.float32)
    X = np.zeros((m, n), np.float32)
    X[rows, cols] = vals

    w_true = np.zeros(m)
    support = rng.choice(min(m, 2_000), size=50, replace=False)
    w_true[support] = rng.standard_normal(50)
    scores = np.bincount(cols, weights=vals * w_true[rows], minlength=n)
    scores += 0.1 * np.std(scores) * rng.standard_normal(n)
    y = np.where(scores >= np.median(scores), 1.0, -1.0).astype(np.float32)
    return X, y


# --------------------------------------------------------------------------
# helpers over results
# --------------------------------------------------------------------------

def certified_gaps(r) -> np.ndarray:
    """Per-step duality gap each step's solution was certified with: an
    upper bound on its objective's distance to the optimum. The host driver
    logs the radius ``delta`` instead; ``delta >= sqrt(2 gap) / lam`` gives
    ``gap <= (lam delta)^2 / 2``. Unknown -> inf."""
    out = []
    for st in r.extras["path_trace"].steps:
        if np.isfinite(st.gap):
            out.append(st.gap)
        elif np.isfinite(st.delta):
            out.append(0.5 * (st.lam * st.delta) ** 2)
        else:
            out.append(np.inf)
    return np.asarray(out, np.float64)


def agreement(a, b) -> dict:
    """Objectives of two solves of the same path agree when they are within
    ``REL_TOL`` relative, or within the sum of their certified gaps (both
    are then provably as close to the one optimum as each certificate
    says: a float32 iterate can stall that far from it, a bug cannot)."""
    pa, pb = np.asarray(a.objectives), np.asarray(b.objectives)
    diff = np.abs(pa - pb)
    rel = diff / np.maximum(np.abs(pb), 1.0)
    bound = certified_gaps(a) + certified_gaps(b)
    ok = (rel < REL_TOL) | (diff <= bound)
    return dict(ok=bool(ok.all()), max_rel=float(rel.max()),
                steps_past_rel_tol=int(np.sum(rel >= REL_TOL)),
                worst_diff_over_gaps=float(np.max(diff / np.maximum(
                    bound, 1e-30))))


def device_peak_gb(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 1e9


def report(phase: str, r, dev, **extra):
    """One line per phase; checks the health words and convergence."""
    health = np.asarray(r.extras["health"])
    line = dict(
        phase=phase, compile_s=COMPILE_S[0],
        wall_s=r.extras.get("total_seconds"),
        iters=np.asarray(r.solver_iters).tolist(),
        kept=np.asarray(r.kept).tolist(),
        health=health.tolist(),
        device_peak_gb=device_peak_gb(dev), **extra)
    say(f"[{phase}]", json.dumps(line))
    COMPILE_S[0] = 0.0
    check(not health.any(), f"{phase}: nonzero health words {health.tolist()}")
    conv = r.extras.get("converged")
    if conv is not None:
        check(bool(np.all(conv)), f"{phase}: unconverged steps {conv}")
    return line


def scan_program_text(X, y, lam_max, static_kw, lambdas, dev) -> str:
    """The compiled HLO of the scan engine's program for exactly the
    arguments ``svm_path_scan`` dispatches (X as a row-major shape on
    ``dev``, as ``place_row_major`` puts it): a compilation-cache hit after
    the dispatch itself, and no second copy of X on the device."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from repro.core.dual import bias_at_lambda_max, theta_at_lambda_max
    from repro.core.path_scan import _engine_jit
    from repro.core.screening import SAFE_TAU

    Xs = jax.ShapeDtypeStruct(X.shape, jnp.float32, sharding=Format(
        Layout(major_to_minor=(0, 1)), SingleDeviceSharding(dev)))
    yd = jnp.asarray(y)
    lam = jnp.asarray(lam_max, jnp.float32)
    args = (Xs, yd, jnp.asarray(lambdas, jnp.float32),
            jnp.zeros((X.shape[0],), jnp.float32), bias_at_lambda_max(yd),
            theta_at_lambda_max(yd, lam), jnp.asarray(0.0, jnp.float32), lam,
            None, float(SAFE_TAU), float(SOLVE["tol"]))
    return _engine_jit(static_kw).lower(*args).compile().as_text()


# --------------------------------------------------------------------------
# the two runs
# --------------------------------------------------------------------------

def run_one_chip(X, y, jobs, dev, record):
    """Scan, unscreened reference, host driver, path server."""
    from repro.core import svm_path
    from repro.core.path import default_lambda_grid
    from repro.launch.path_server import PathServer

    scan = svm_path(X, y, engine="scan", reduce="compact", **GRID, **SOLVE)
    static_kw = scan.extras["options"]
    lambdas = default_lambda_grid(scan.extras["lam_max"], **GRID)
    kernels = scan_program_text(X, y, scan.extras["lam_max"],
                                tuple(static_kw.items()), lambdas, dev
                                ).count("tpu_custom_call")
    record["scan"] = report("scan", scan, dev, tpu_custom_calls=kernels,
                            use_pallas=static_kw["use_pallas"],
                            caps=np.asarray(scan.extras["caps"]).tolist())
    check(static_kw["use_pallas"], "scan: the default policy did not pick "
          "the Pallas kernels on the TPU")
    check(kernels > 0, "scan: compiled program has no tpu_custom_call")

    ref = svm_path(X, y, engine="scan", reduce="compact", screening=False,
                   **GRID, **SOLVE)
    keep = np.asarray(scan.extras["keep_masks"], bool)
    w_ref = np.asarray(ref.weights)
    unsafe = [int(np.count_nonzero(w_ref[t][~keep[t]]))
              for t in range(len(keep))]
    agree = agreement(scan, ref)
    record["reference"] = report("reference", ref, dev, vs_scan=agree,
                                 discarded_but_nonzero=unsafe)
    check(agree["ok"], f"reference: scan objectives disagree, {agree}")
    check(not any(unsafe), f"reference: discarded features nonzero in the "
          f"unscreened solution, per step {unsafe}")

    t0 = time.perf_counter()
    host = svm_path(X, y, engine="host", **GRID, **SOLVE)
    host.extras["total_seconds"] = time.perf_counter() - t0
    agree = agreement(host, scan)
    record["host"] = report("host", host, dev, vs_scan=agree)
    check(agree["ok"], f"host: objectives disagree with the scan, {agree}")

    server = PathServer(slots=2, tol=SOLVE["tol"], max_iters=SOLVE["max_iters"])
    t0 = time.perf_counter()
    results = server.serve(jobs, log=lambda msg: say("[server]", msg))
    wall = time.perf_counter() - t0
    agrees = []
    for job, res in zip(jobs, results):
        check(job.status == "done" and res is not None,
              f"server: job {job.jid} ended {job.status!r} ({job.error})")
        own = svm_path(job.X, job.y, engine="scan", reduce="compact",
                       n_lambdas=job.n_lambdas,
                       lam_min_ratio=job.lam_min_ratio, **SOLVE)
        res.extras["total_seconds"] = wall
        agrees.append(agreement(res, own))
        report(f"server.job{job.jid}", res, dev, vs_own_path=agrees[-1])
    record["server"] = dict(server.last_serve, vs_own_path=agrees)
    say("[server]", json.dumps(record["server"], default=str))
    check(all(a["ok"] for a in agrees),
          f"server: jobs disagree with their own paths, {agrees}")


def run_four_chips(X, y, devs, record):
    """The sharded scan on two meshes against the one-chip XLA scan."""
    from repro.core import svm_path_scan, svm_path_scan_sharded
    from repro.core.distributed import svm_mesh

    x_gb = X.nbytes / 1e9
    sharded = {}
    for shape in ((1, 4), (2, 2)):
        r = svm_path_scan_sharded(svm_mesh(*shape), X, y, **GRID, **SOLVE)
        sharded[shape] = r
        record[f"mesh{shape}"] = report(
            f"sharded{shape}", r, devs[0],
            device_peaks_gb=[device_peak_gb(d) for d in devs])
    peak0 = device_peak_gb(devs[0])
    check(peak0 < x_gb, f"device 0 peaked at {peak0} GB >= X's {x_gb} GB: "
          "X was materialized whole on one device")
    one = svm_path_scan(X, y, use_pallas=False, reduce="mask", **GRID,
                        **SOLVE)
    record["one_chip_xla"] = report("one_chip_xla", one, devs[0])
    for shape, r in sharded.items():
        agree = agreement(r, one)
        record[f"mesh{shape}"]["vs_one_chip"] = agree
        say(f"[compare] mesh{shape} vs_one_chip={json.dumps(agree)}")
        check(agree["ok"], f"mesh{shape}: objectives disagree with the "
              f"one-chip scan, {agree}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    def refuse(msg):
        print(f"chip_smoke: {msg}", file=sys.stderr)
        return 1

    if os.environ.get("REPRO_PALLAS_INTERPRET", "") != "":
        return refuse("REPRO_PALLAS_INTERPRET is set: the kernels would run "
                      "interpreted, not on the chip")
    if os.environ.get("REPRO_FISTA_PALLAS", "") == "0":
        return refuse("REPRO_FISTA_PALLAS=0 turns the kernels off")
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        return refuse(f"the program is not next to this script ({src})")
    sys.path.insert(0, str(src))

    import jax

    from repro.compile_cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return refuse(f"no TPU found (JAX reports {devs[0].platform!r})")
    if len(devs) < args.chips:
        return refuse(f"--chips {args.chips} needs {args.chips} devices, "
                      f"JAX reports {len(devs)}")
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    say(f"[setup] device={devs[0].device_kind} count={len(devs)} "
        f"jax={jax.__version__} compile_cache={cache_dir} "
        f"host_peak_rss_gb={host_peak_rss_gb()}")

    record = dict(chips=args.chips, seed=args.seed, cache_dir=cache_dir)
    t0 = time.perf_counter()
    X, y = rcv1_like(args.seed, **RCV1)
    record["data"] = dict(shape=list(X.shape), nnz=int(np.count_nonzero(X)),
                          gen_s=time.perf_counter() - t0,
                          host_peak_rss_gb=host_peak_rss_gb())
    say("[data]", json.dumps(record["data"]))

    try:
        if args.chips == 4:
            run_four_chips(X, y, devs[:4], record)
        else:
            from repro.launch.path_server import PathJob

            jobs = []
            for i in range(4):
                Xj, yj = rcv1_like(args.seed + 1 + i, **SERVER_JOB)
                jobs.append(PathJob(jid=i, X=Xj, y=yj, **GRID))
            run_one_chip(X, y, jobs, devs[0], record)
    except SmokeFailure as e:
        return refuse(f"FAILED: {e}")
    finally:
        record["host_peak_rss_gb"] = host_peak_rss_gb()
        out = Path(__file__).resolve().parent / "artifacts"
        out.mkdir(exist_ok=True)
        (out / f"chip_smoke_{args.chips}.json").write_text(
            json.dumps(record, indent=1, default=str))

    say(f"[done] host_peak_rss_gb={record['host_peak_rss_gb']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
