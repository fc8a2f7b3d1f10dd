#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload rcv1.glmnet50 --seed 7 --seconds 45 --trace 0

Run from the root of a checkout. The cell's file ``bench/workloads/<cell>.json``
names its configuration (``bench/configs/<config>.json``), whose generator
(``bench/gen/<generator>.py``) makes X and y on the device from ``--seed``,
and the entry (``bench/entries/<entry>.py``) that one call of the window
drives. Set-up makes the data, builds the grid and warms the entry's
programs; the window then calls the entry back to back for ``--seconds``
and up to the end of the call running then.

``--trace 0`` reports the end-to-end metrics. ``--trace 1`` traces a few
calls with the JAX profiler instead and reports the cell's per-layer
metrics, each read by ``bench/metrics/<metric>.py``, with the device's busy
time and a breakdown. A traced run compiles its programs without the
persistent cache, with XLA dumping their optimized HLO as text to a
temporary directory: the trace names each device op by its HLO
instruction, and the dump gives the instruction's name scope
(``trace_reduce.py``). Either way, what the calls returned is then compared
with the plain reference (``reference.py``, ``check.py``), and the numbers
compared are printed beside their limits: as the last lines on standard
error, and under ``checks``, the last key of the result. The result is the
last line on standard output.

A reader returns the metric's value, or ``None`` where the run gave it
nothing to read; it raises ``LookupError`` where the program under test has
no counter or scope of the kind it reads at all. Such a metric is left out
of the result, with a ``[metric] <name> absent from this program`` line, so
a cell can list a metric that a later program adds.

Without a TPU, on fewer chips than the cell asks for, on a chip missing
from ``bench/peaks.json``, with ``REPRO_PALLAS_INTERPRET`` set or with
``REPRO_FISTA_PALLAS=0``, it prints no result and exits 1; so it does when a
reader of a per-layer metric that ``BENCHMARK.json`` lists for the cell
returns ``None``.

A configuration's file may carry other keys than those read here:
``cpu_shapes`` (``{"tiny": shape, "mid": shape, "mid_limits": limits}``,
``mid`` and ``mid_limits`` optional) sizes it for the CPU rehearsal,
``test_bench.py``. So a new configuration, its cells and its metrics enter
the benchmark as new files and new entries of ``BENCHMARK.json`` alone.

Two options serve the readings limits are set from, never the benchmark's
own runs: ``--entry control_high`` drives the lower-precision control in the
cell entry's place (``calibrate.py``), and ``--keep-trace DIR`` copies the
traced run's profile to ``DIR``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: paths the traced run records, one profiler span each
TRACED_PATHS = 2


class Refusal(Exception):
    """The run cannot measure what the cell asks for."""


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """The module in ``path``, imported under its file's stem."""
    if not path.is_file():
        raise Refusal(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(*parts):
    print(*parts, flush=True)


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64."""
    import jax

    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed >> 32)


def refuse_environment():
    if os.environ.get("REPRO_PALLAS_INTERPRET", "") != "":
        raise Refusal("REPRO_PALLAS_INTERPRET is set: the kernels would run "
                      "interpreted, not on the chip")
    if os.environ.get("REPRO_FISTA_PALLAS", "") == "0":
        raise Refusal("REPRO_FISTA_PALLAS=0 turns the kernels off")
    if not (ROOT / "src" / "repro").is_dir():
        raise Refusal(f"the system under test is not in {ROOT / 'src'}")


def require_chip(chips: int, peaks: dict):
    """``(devices, peaks of their kind)``: the cell's chips, or a refusal."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refusal(f"no TPU found (JAX reports {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refusal(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refusal(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks[kind]


class CompileCounter:
    """Counts JAX's tracing and compiling events (``jax.monitoring``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, secs: float, **_):
        if event in self.EVENTS:
            self.count += 1


_COMPILES: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one counter, registered with JAX on first use."""
    global _COMPILES
    if _COMPILES is None:
        import jax

        _COMPILES = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
    return _COMPILES


def enable_cache() -> str:
    """JAX's persistent compilation cache in :data:`CACHE_DIR`, every
    program cached; the program under test is given the same directory."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)


def stop_cache_writes() -> None:
    """Write no program compiled from here on to the persistent cache.

    A program of the path engine takes X in the row-major layout it places
    X in (``repro.kernels.ops.place_row_major``). Loaded from the cache in a
    later process, such a program expects X in the default layout and
    refuses the row-major buffer (``INVALID_ARGUMENT: Executable ...
    expected parameter 0 ... {0,1} but got ... {1,0}``). So only the
    benchmark's own set-up programs, compiled before the entry runs, are
    cached; nothing written, nothing read after this."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float("inf"))


def dump_hlo(hlo_dir: str) -> None:
    """Compile every program anew, with XLA dumping its optimized HLO as
    text to ``hlo_dir``. Takes effect only before JAX starts its backend;
    a program loaded from the persistent cache would not be dumped."""
    import jax

    flags = f"--xla_dump_to={hlo_dir} --xla_dump_hlo_as_text"
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flags}"
    jax.config.update("jax_enable_compilation_cache", False)


def window(entry, seconds: float):
    """Calls back to back until the first one that ends after ``seconds``.
    Returns ``(results, errors, attempted, elapsed_s)``."""
    results, errors, attempted = [], [], 0
    t0 = time.perf_counter()
    while True:
        attempted += 1
        try:
            results.append(entry.call())
        except Exception:  # a call that raises is a failed call; go on
            errors.append(traceback.format_exc())
        if time.perf_counter() - t0 >= seconds:
            break
    return results, errors, attempted, time.perf_counter() - t0


def traced(entry, n_calls: int, trace_dir: str):
    """``n_calls`` calls under the profiler, each in a ``bench.path`` span
    and followed by a ``bench.between_paths`` span."""
    import jax

    results, errors = [], []
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(n_calls):
            with jax.profiler.TraceAnnotation("bench.path"):
                try:
                    r = entry.call()
                except Exception:  # a call that raises is a failed call
                    r = None
                    errors.append(traceback.format_exc())
            with jax.profiler.TraceAnnotation("bench.between_paths"):
                if r is not None:
                    results.append(r)
    finally:
        jax.profiler.stop_trace()
    return results, errors


def per_layer_metrics(bench: dict, cell: str) -> list:
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def run(args, chip_check=None) -> dict:
    """One run of the cell; returns the result line's object.
    ``chip_check`` stands in for :func:`require_chip`."""
    bench = read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise Refusal(f"no workload {args.workload!r} in BENCHMARK.json")
    wl = read_json(BENCH / "workloads" / f"{args.workload}.json")
    cfg = read_json(BENCH / "configs" / f"{wl['config']}.json")
    refuse_environment()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))

    hlo_dir = None
    if args.trace:
        hlo_dir = tempfile.mkdtemp(prefix="bench_hlo_")
        dump_hlo(hlo_dir)
    try:
        return _run(args, bench, cells, wl, cfg, hlo_dir, chip_check)
    finally:
        if hlo_dir:
            shutil.rmtree(hlo_dir, ignore_errors=True)


def _run(args, bench, cells, wl, cfg, hlo_dir, chip_check) -> dict:
    import jax
    import numpy as np

    devs, peaks = (chip_check or require_chip)(
        cells[args.workload]["chips"], read_json(BENCH / "peaks.json"))
    import check
    import reference

    cache_dir = "off (traced run)" if args.trace else enable_cache()
    compiles = compile_counter()

    gen = load_module(BENCH / "gen" / f"{cfg['generator']}.py")
    t = time.perf_counter()
    X, y = jax.block_until_ready(
        gen.generate(seed_key(args.seed), cfg["shape"],
                     cfg["generator_params"]))
    gen_s = time.perf_counter() - t
    lam_max = float(reference.lambda_max(X, y))
    lambdas = reference.lambda_grid(lam_max, wl["grid"])
    stop_cache_writes()
    entry_name = args.entry or wl["entry"]
    entry = load_module(BENCH / "entries" / f"{entry_name}.py").Entry(
        X, y, lambdas, wl["kwargs"])
    t = time.perf_counter()
    entry.warm()
    warm_s = time.perf_counter() - t
    setup_s = time.monotonic() - T_START
    say(f"[setup] device={devs[0].device_kind} chips={len(devs)} "
        f"jax={jax.__version__} compile_cache={cache_dir} gen_s={gen_s:.3f} "
        f"warm_s={warm_s:.3f} setup_s={setup_s:.3f} steps={len(lambdas)} "
        f"lam_max={lam_max!r} entry={entry_name}")

    metrics, device_extra, breakdown = {}, {}, None
    n_before = compiles.count
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
            results, errors = traced(entry, TRACED_PATHS, d)
            import trace_reduce

            tr = trace_reduce.reduce_dir(d, devs, hlo_dir)
            if args.keep_trace:
                shutil.copytree(d, args.keep_trace, dirs_exist_ok=True)
                shutil.copytree(hlo_dir, Path(args.keep_trace) / "hlo",
                                dirs_exist_ok=True)
        attempted = TRACED_PATHS
        say(f"[trace] {json.dumps(tr)}")
    else:
        results, errors, attempted, elapsed = window(entry, args.seconds)
        tr = None
        if results:
            metrics["path_s"] = {"value": elapsed / len(results), "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        say(f"[window] paths={len(results)} attempted={attempted} "
            f"elapsed_s={elapsed!r}")
    say(f"[window] compile_events={compiles.count - n_before}")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    summaries = [entry.summary(r) for r in results]
    del results
    for e in errors:
        print(e, file=sys.stderr)
    failed = len(errors) + sum(1 for s in summaries
                               if not (s["healthy"] and s["converged"]))
    if summaries:
        s = summaries[-1]
        say(f"[path] iters={s['iters'].tolist()} kept={s['kept'].tolist()} "
            f"healthy={s['healthy']} converged={s['converged']}")

    if args.trace:
        ctx = SimpleNamespace(paths=summaries, trace=tr, peaks=peaks)
        for m in per_layer_metrics(bench, args.workload):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            try:
                v = reader.read(ctx)
            except LookupError:
                say(f"[metric] {m['name']} absent from this program")
                continue
            if v is None:
                raise Refusal(f"per-layer metric {m['name']} found nothing "
                              f"to read in this run of {args.workload}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device_extra = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}

    # the comparison, once the window is closed and its peak read
    t = time.perf_counter()
    ref = jax.block_until_ready(reference.solve_path(
        X, y, lambdas, wl["kwargs"]["tol"],
        max_iters=wl["kwargs"]["max_iters"]))
    values = check.readings(X, y, lambdas, [s["outputs"] for s in summaries],
                            ref) if summaries else {}
    correct, checks = check.verdict(values, wl["limits"])
    correct = correct and bool(summaries) and not errors
    say(f"[check] reference_s={time.perf_counter() - t:.3f} "
        f"reference_iters={int(np.sum(ref.iters))} readings={json.dumps(values)}")

    out = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(memory_peak), **device_extra},
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["readings"] = values
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--entry", default=None,
                    help="an entry of bench/entries/ in place of the cell's")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's profile to this directory")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except Refusal as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
