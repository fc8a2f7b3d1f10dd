"""CPU rehearsal of the benchmark at tiny sizes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/test_bench.py

Covers the generators, the plain reference, the comparison, the reduction
of a trace, the lower-precision control, and whole runs of ``run.py`` with
the chip check skipped: sound, and with the timed path broken underneath in
each way a path can be.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402

import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

TINY = {
    "rcv1": {"features": 600, "samples": 300, "density": 0.02},
}
#: a size at which the CPU separates the control from the program on the
#: cell's whole grid (``test_control_is_not_correct``)
MID = {
    "rcv1": {"features": 6000, "samples": 3000, "density": 0.006},
}
#: the cells of BENCHMARK.json
RUN_CELLS = [w["name"] for w in run.read_json(run.ROOT / "BENCHMARK.json")
             ["workloads"]]


def tiny_json(path: Path, shapes=TINY):
    """``run.read_json`` with configurations cut to ``shapes`` and grids
    to their first 8 points."""
    with open(path) as f:
        d = json.load(f)
    if path.parent.name == "configs":
        d = {**d, "shape": shapes[d["name"]]}
    elif path.parent.name == "workloads":
        d = {**d, "grid": {**d["grid"], "take": min(8, d["grid"]["take"])}}
    return d


def cpu_chip(chips, peaks):
    return jax.devices()[:chips], peaks["TPU v5 lite"]


def data(config: str, seed: int):
    cfg = run.read_json(run.BENCH / "configs" / f"{config}.json")
    gen = run.load_module(run.BENCH / "gen" / f"{cfg['generator']}.py")
    return gen.generate(run.seed_key(seed), TINY[config],
                        cfg["generator_params"])


@pytest.mark.parametrize("config", sorted(TINY))
def test_generator_is_seeded_and_unit_norm(config):
    X, y = data(config, 2 ** 31 + 5)
    X2, y2 = data(config, 2 ** 31 + 5)
    X3, _ = data(config, 6)
    shape = TINY[config]
    assert X.shape == (shape["features"], shape["samples"])
    assert X.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    assert not np.array_equal(np.asarray(X), np.asarray(X3))
    norms = np.linalg.norm(np.asarray(X), axis=0)
    live = norms > 0
    np.testing.assert_allclose(norms[live], 1.0, rtol=1e-5)
    assert set(np.unique(np.asarray(y))) == {-1.0, 1.0}
    assert abs(float(np.mean(np.asarray(y)))) < 0.1
    if "density" in shape:
        density = np.count_nonzero(np.asarray(X)) / X.size
        assert 0.5 * shape["density"] < density <= shape["density"]


def test_lambda_grid_is_glmnets():
    lams = reference.lambda_grid(2.0, {"total": 100, "min_ratio": 0.01,
                                       "take": 50})
    assert len(lams) == 50 and lams[0] == 2.0
    np.testing.assert_allclose(lams[1:] / lams[:-1], 0.01 ** (1 / 99))
    np.testing.assert_allclose(lams[-1], 2.0 * 0.01 ** (49 / 99))


def test_reference_certifies_its_own_path():
    X, y = data("rcv1", 3)
    lams = reference.lambda_grid(float(reference.lambda_max(X, y)),
                                 {"total": 100, "min_ratio": 0.01, "take": 8})
    ref = reference.solve_path(X, y, lams, 1e-9, max_iters=20000)
    assert np.all(np.asarray(ref.converged))
    obj = np.asarray(ref.obj)
    p = reference.objectives64(X, y, ref.w, ref.b, np.float32(lams))
    np.testing.assert_allclose(obj, p, rtol=1e-5)
    # weak duality: the gap is nonnegative up to rounding, and small
    assert np.all(np.asarray(ref.gap) > -1e-4 * obj)
    assert np.all(np.asarray(ref.gap) < 1e-2 * obj)
    # w = 0 at lambda_max, nonzero below it
    assert np.count_nonzero(np.asarray(ref.w)[0]) == 0
    assert np.count_nonzero(np.asarray(ref.w)[-1]) > 0


def test_verdict_fails_a_number_over_its_limit_or_missing():
    ok, checks = check.verdict({"a": 1.0, "b": 0}, {"a": 2.0, "b": 0})
    assert ok and checks["a"] == {"value": 1.0, "limit": 2.0}
    assert not check.verdict({"a": 3.0}, {"a": 2.0})[0]
    assert not check.verdict({}, {"a": 2.0})[0]
    assert not check.verdict({"a": float("nan")}, {"a": 2.0})[0]


# -- the trace reduction ---------------------------------------------------

HLO_TEXT = """HloModule jit_scan, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(scan)/while/body/svm_path/screen/sin"}
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.2 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(scan)/while/body/svm_path/solve/a"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(scan)/while/body/svm_path/solve/b"}
  ROOT %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation
}

ENTRY %main.5 (x.1: f32[8]) -> f32[8] {
  ROOT %while.1 = (s32[], f32[8]{0}) while(%tuple), condition=%cond.1, body=%body.1, metadata={op_name="jit(scan)/while"}
  %copy.5 = f32[8]{0} copy(%x.1)
}
"""


def test_module_scopes_reads_op_names_and_called_computations():
    module, scopes, names = trace_reduce.module_scopes(HLO_TEXT)
    assert module == "jit_scan"
    # its own op_name first; with none, the first scope it calls
    assert scopes == {"sine.1": "svm_path/screen", "fusion.2": "svm_path/solve",
                      "fusion.3": "svm_path/solve", "fusion.4": "svm_path/screen",
                      "while.1": "svm_path/solve"}
    assert names == set(scopes) | {"param_0", "copy.5"}


def test_trace_reduce_counts_leaves_and_labels_gaps(tmp_path):
    (tmp_path / "module_0001.jit_scan.tpu_after_optimizations.txt").write_text(
        HLO_TEXT)
    # another module of the same name, whose instructions did not run
    (tmp_path / "module_0002.jit_scan.tpu_after_optimizations.txt").write_text(
        "HloModule jit_scan\n\nENTRY %main (x: f32[8]) -> f32[8] {\n"
        '  ROOT %fusion.3 = f32[8]{0} fusion(%x), metadata={op_name="svm_path/screen/z"}\n'
        "}\n")

    class Ev:
        def __init__(self, name, s, e):
            self.name, self.start_ns, self.end_ns = name, s, e
            self.duration_ns, self.stats = e - s, ()

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    def op(instruction, s, e):
        return Ev(f"%{instruction} = f32[8]{{0}} fusion(%x)", s, e)

    class PD:
        planes = [
            Plane("/host:CPU", [Line("python", [
                Ev("bench.path", 0, 100), Ev("bench.between_paths", 100, 120),
                Ev("bench.path", 120, 200)])]),
            Plane("/device:TPU:0", [
                Line("XLA Modules", [Ev("jit_scan(77)", 5, 85),
                                     Ev("jit_other(78)", 140, 195)]),
                Line("XLA Ops", [
                    op("while.1", 10, 60), op("fusion.2", 10, 30),
                    op("fusion.3", 40, 60), op("fusion.4", 70, 80),
                    op("fusion.3", 150, 190)])]),
        ]

    tr = trace_reduce.reduce(PD(), hlo=trace_reduce.read_hlo_dir(str(tmp_path)))
    assert tr["paths"] == 2 and tr["window_s"] == pytest.approx(200e-9)
    assert tr["busy_s"] == pytest.approx(100e-9)
    assert tr["scopes"]["svm_path/solve"] == pytest.approx(40e-9)
    assert tr["scopes"]["svm_path/screen"] == pytest.approx(10e-9)
    # an instruction of another module is not taken for jit_scan's
    assert tr["scopes"]["other"] == pytest.approx(40e-9)
    assert tr["device_ops"][0] == ["svm_path/solve:fusion", pytest.approx(40e-9)]
    # the gap 80..150 has its middle in the span between the paths
    assert tr["idle_gaps"][0] == ["bench.between_paths", pytest.approx(70e-9)]
    assert sorted(g[1] for g in tr["idle_gaps"][1:]) == pytest.approx(
        [10e-9] * 3)
    assert {g[0] for g in tr["idle_gaps"][1:]} == {"bench.path"}


def test_trace_reduce_reads_a_recorded_tpu_trace(tmp_path):
    """A trace recorded on a TPU v5e (the cell's path at 2,048 x 1,024, 20
    steps, two paths) with the optimized HLO of its scan program: every
    phase of the path is found, the Pallas sweeps under the solve."""
    import gzip

    from jax.profiler import ProfileData

    fixture = BENCH / "fixtures" / "trace_v5e_tiny"
    for gz in fixture.glob("*.txt.gz"):
        (tmp_path / gz.name[:-3]).write_bytes(gzip.open(gz).read())
    pd = ProfileData.from_serialized_xspace(
        gzip.open(fixture / "trace.xplane.pb.gz").read())
    tr = trace_reduce.reduce(pd, [0], trace_reduce.read_hlo_dir(str(tmp_path)))
    assert tr["paths"] == 2 and tr["planes"] == 1
    assert 0 < tr["busy_s"] <= tr["window_s"]
    assert set(tr["scopes"]) == {"svm_path/screen", "svm_path/solve",
                                 "svm_path/certify", "other"}
    assert tr["scopes"]["svm_path/solve"] > 0.5 * tr["busy_s"]
    assert sum(tr["scopes"].values()) <= tr["busy_s"] * (1 + 1e-9)
    top = [name for name, _ in tr["device_ops"][:2]]
    assert set(top) == {"svm_path/solve:hinge_margin_pallas",
                        "svm_path/solve:hinge_grad_pallas"}


# -- the control and the faults ---------------------------------------------

def test_control_is_not_correct(monkeypatch):
    """The lower-precision control (``entries/control_high.py``), run by
    ``run.py`` in the program's place on the cell's whole grid at a size
    the CPU holds, comes out not correct; the program on the same data
    comes out correct.

    At this size on the CPU the two separate on ``obj_excess`` (program
    1.1e-6, control 1.5e-4) and not on ``obj_report`` (2.1e-7 and 4.6e-7),
    so the run is judged under a limit on ``obj_excess`` set between those
    readings. At the cell's size on the chip it is the other way round, and
    the workload's limit is on ``obj_report`` (``PERF.md`` section 2)."""
    cell = RUN_CELLS[0]
    take = run.read_json(run.BENCH / "workloads" / f"{cell}.json")["grid"][
        "take"]
    limits = {"discarded_nonzero": 0, "obj_excess": 1e-5}
    program = run_cell(monkeypatch, cell, take=take, seed=11, shapes=MID,
                       limits=limits)
    control = run_cell(monkeypatch, cell, take=take, seed=11, shapes=MID,
                       entry="control_high", limits=limits)
    assert program["correct"], program["checks"]
    assert not control["correct"], control["checks"]
    assert control["readings"]["obj_report"] > program["readings"][
        "obj_report"]


def test_calibrate_prints_one_line_per_run(monkeypatch, capsys):
    import calibrate

    monkeypatch.setattr(run, "read_json", tiny_json)
    monkeypatch.setattr(run, "require_chip", cpu_chip)
    assert calibrate.main(["--workload", RUN_CELLS[0], "--seeds", "1", "2",
                           "--control-seeds", "3"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"seed"')]
    assert [(ln["seed"], ln["entry"]) for ln in lines] == [
        (1, "cell"), (2, "cell"), (3, "control_high")]
    limits = run.read_json(run.BENCH / "workloads" / f"{RUN_CELLS[0]}.json"
                           )["limits"]
    assert all(set(limits) <= set(ln["readings"]) for ln in lines)


def _stale_step(r):
    """Each step returns the state it was given: the previous step's."""
    for a in (r.weights, r.biases, r.objectives):
        a[1:] = a[:-1].copy()
    return r


def _alter_answer(r):
    """One kept weight of the last step is changed where it is produced."""
    j = int(np.argmax(np.abs(r.weights[-1])))
    r.weights[-1, j] *= 1.5
    return r


def _discard_live(r):
    """The last step's keep mask drops a feature that is live."""
    j = int(np.argmax(np.abs(r.weights[-1])))
    r.extras["keep_masks"] = np.array(r.extras["keep_masks"])
    r.extras["keep_masks"][-1, j] = False
    r.weights[-1, j] = 0.0
    return r


FAULTS = {"stale_step": _stale_step, "altered_answer": _alter_answer,
          "discarded_live_feature": _discard_live, "half_samples": None}


def run_cell(monkeypatch, cell, fault=None, trace=0, entry=None, take=8,
             seed=2 ** 32 + 9, shapes=TINY, limits=None):
    import repro.core

    def read_json(path: Path):
        d = tiny_json(path, shapes)
        if path.parent.name == "workloads":
            d = {**d, "grid": {**d["grid"], "take": take},
                 "limits": limits or d["limits"]}
        return d

    monkeypatch.setattr(run, "read_json", read_json)
    real = repro.core.svm_path
    if fault == "half_samples":
        def broken(X, y, **kw):
            # half of the samples left out, the loss taken over the rest
            h = X.shape[1] // 2
            return real(X[:, :h], y[:h], **kw)
    elif fault is not None:
        def broken(X, y, **kw):
            return FAULTS[fault](real(X, y, **kw))
    if fault is not None:
        monkeypatch.setattr(repro.core, "svm_path", broken)
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", str(trace)]
                     + (["--entry", entry] if entry else []))
    return run.run(args, chip_check=cpu_chip)


@pytest.mark.parametrize("cell", RUN_CELLS)
def test_sound_run_is_correct(monkeypatch, cell):
    out = run_cell(monkeypatch, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"path_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] >= 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", RUN_CELLS)
def test_broken_path_is_not_correct(monkeypatch, cell, fault):
    out = run_cell(monkeypatch, cell, fault)
    assert not out["correct"], out["checks"]


def test_traced_run_refuses_a_listed_metric_it_cannot_read(monkeypatch):
    """A CPU trace has no device plane, so the readers of device time find
    nothing: the run stops instead of leaving the metrics out."""
    with pytest.raises(run.Refusal, match="found nothing to read"):
        run_cell(monkeypatch, RUN_CELLS[0], trace=1)


def test_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", RUN_CELLS[0], "--seed", "1",
                     "--seconds", "1"]) == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "no TPU" in cap.err


def test_refuses_pallas_interpret(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert run.main(["--workload", RUN_CELLS[0], "--seed", "1",
                     "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""
