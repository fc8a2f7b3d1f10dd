"""CPU rehearsal of the benchmark at tiny sizes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/test_bench.py

Covers the generators, the plain reference, the comparison, the reduction
of a trace, the lower-precision control, and whole runs of ``run.py`` with
the chip check skipped: sound, and with the timed path broken underneath in
each way a path can be.

Every configuration and cell of ``BENCHMARK.json`` is covered from its own
files: a configuration's ``cpu_shapes`` gives the ``tiny`` size every run
here is cut to, and, where the CPU separates the lower-precision control
from the program on the cell's whole grid, a ``mid`` size and the
``mid_limits`` it is judged under there (the workload's own limits where
none are given).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

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

BENCHMARK = run.read_json(run.ROOT / "BENCHMARK.json")
#: the configurations and the cells of BENCHMARK.json
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]
RUN_CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def cpu_shapes(config: str) -> dict:
    """The configuration file's ``cpu_shapes``."""
    with open(run.BENCH / "configs" / f"{config}.json") as f:
        return json.load(f)["cpu_shapes"]


#: configurations with a size at which the CPU separates the control from
#: the program (``test_control_is_not_correct``)
MID_CONFIGS = [c for c in CONFIGS if "mid" in cpu_shapes(c)]


def tiny_json(path: Path, size="tiny"):
    """``run.read_json`` with configurations cut to their ``cpu_shapes``
    entry ``size`` and grids to their first 8 points."""
    with open(path) as f:
        d = json.load(f)
    if path.parent.name == "configs":
        d = {**d, "shape": d["cpu_shapes"][size]}
    elif path.parent.name == "workloads":
        d = {**d, "grid": {**d["grid"], "take": min(8, d["grid"]["take"])}}
    return d


def cpu_chip(chips, peaks):
    return jax.devices()[:chips], peaks["TPU v5 lite"]


def data(config: str, seed: int):
    cfg = run.read_json(run.BENCH / "configs" / f"{config}.json")
    gen = run.load_module(run.BENCH / "gen" / f"{cfg['generator']}.py")
    return gen.generate(run.seed_key(seed), cfg["cpu_shapes"]["tiny"],
                        cfg["generator_params"])


@pytest.mark.parametrize("config", CONFIGS)
def test_generator_is_seeded_and_unit_norm(config):
    X, y = data(config, 2 ** 31 + 5)
    X2, y2 = data(config, 2 ** 31 + 5)
    X3, _ = data(config, 6)
    shape = cpu_shapes(config)["tiny"]
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


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_certifies_its_own_path(config):
    X, y = data(config, 3)
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


class Profile:
    """A stand-in for ``jax.profiler.ProfileData``."""

    def __init__(self, planes):
        self.planes = planes


def op(instruction, s, e):
    return Ev(f"%{instruction} = f32[8]{{0}} fusion(%x)", s, e)


def test_trace_reduce_counts_leaves_and_labels_gaps(tmp_path):
    (tmp_path / "module_0001.jit_scan.tpu_after_optimizations.txt").write_text(
        HLO_TEXT)
    # another module of the same name, whose instructions did not run
    (tmp_path / "module_0002.jit_scan.tpu_after_optimizations.txt").write_text(
        "HloModule jit_scan\n\nENTRY %main (x: f32[8]) -> f32[8] {\n"
        '  ROOT %fusion.3 = f32[8]{0} fusion(%x), metadata={op_name="svm_path/screen/z"}\n'
        "}\n")
    pd = Profile([
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
    ])

    tr = trace_reduce.reduce(pd, hlo=trace_reduce.read_hlo_dir(str(tmp_path)))
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


#: nested scopes as the path engine opens them: a sub-scope of a phase, a
#: phase's scope restated in full inside a ``lax.switch`` branch, an op in a
#: branch with no name of its own, merged names, JAX's own segments
STACK = "jit(path)/while/body/closed_call/svm_path"
HLO_NESTED = f"""HloModule jit_path, is_scheduled=true

%fc (p.0: f32[8]) -> f32[8] {{
  %p.0 = f32[8]{{0}} parameter(0)
  ROOT %add.1 = f32[8]{{0}} add(%p.0, %p.0), metadata={{op_name="{STACK}/solve/cond/branch_1_fun/svm_path/solve/compact/add"}}
}}

%branch_0 (p.1: f32[8]) -> f32[8] {{
  %p.1 = f32[8]{{0}} parameter(0)
  %gather.1 = f32[8]{{0}} gather(%p.1), metadata={{op_name="{STACK}/solve/cond/branch_0_fun/svm_path/solve/compact/jit(_take)/gather"}}
  ROOT %fusion.10 = f32[8]{{0}} fusion(%gather.1), kind=kLoop, calls=%fc, metadata={{op_name="{STACK}/solve/cond/branch_0_fun/while/body/mul"}}
}}

%branch_1 (p.2: f32[8]) -> f32[8] {{
  %p.2 = f32[8]{{0}} parameter(0)
  ROOT %fusion.11 = f32[8]{{0}} fusion(%p.2), kind=kLoop, calls=%fc
}}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %fusion.20 = f32[8]{{0}} fusion(%gte.1), kind=kLoop, calls=%fc, metadata={{op_name="{STACK}/screen/mul;while/body/closed_call"}}
  %conditional.1 = f32[8]{{0}} conditional(%idx, %x, %x), branch_computations={{%branch_0, %branch_1}}, metadata={{op_name="{STACK}/solve/cond"}}
  %fusion.21 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fc, metadata={{op_name="{STACK}/certify/feasibility/while/body/dot_general"}}
  ROOT %fusion.22 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fc, metadata={{op_name="{STACK}/certify/mul"}}
}}

ENTRY %main.3 (x: f32[8]) -> f32[8] {{
  %fusion.30 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fc, metadata={{op_name="jit(path)/svm_path/prologue/jit(_normal)/jit(_normal_real)/erf_inv"}}
  ROOT %while.2 = (s32[], f32[8]{{0}}) while(%tuple), condition=%cond.2, body=%body.2, metadata={{op_name="jit(path)/while"}}
  %copy.3 = f32[8]{{0}} copy(%x)
}}
"""


def test_module_scopes_gives_nested_scopes_their_deepest_path():
    module, scopes, _ = trace_reduce.module_scopes(HLO_NESTED)
    assert module == "jit_path"
    assert scopes == {
        "add.1": "svm_path/solve/compact",
        "gather.1": "svm_path/solve/compact",
        "fusion.10": "svm_path/solve",
        # in a switch branch, with no name of its own: what it calls
        "fusion.11": "svm_path/solve/compact",
        "fusion.20": "svm_path/screen",
        "conditional.1": "svm_path/solve",
        "fusion.21": "svm_path/certify/feasibility",
        "fusion.22": "svm_path/certify",
        "fusion.30": "svm_path/prologue",
        "while.2": "svm_path/screen",
    }
    assert trace_reduce.scope_of("jit(f)/svm_path_batched/solve/mul") is None
    assert trace_reduce.scope_of("jit(f)/svm_path/mul") is None


def test_trace_reduce_totals_each_scope_with_its_sub_scopes(tmp_path):
    (tmp_path / "module_0001.jit_path.tpu_after_optimizations.txt"
     ).write_text(HLO_NESTED)
    leaves = [("fusion.30", 0, 10), ("fusion.20", 10, 20),
              ("gather.1", 20, 30), ("fusion.10", 30, 45),
              ("fusion.11", 45, 50), ("fusion.21", 50, 65),
              ("fusion.22", 65, 75), ("copy.3", 75, 85)]
    pd = Profile([
        Plane("/host:CPU", [Line("python", [Ev("bench.path", 0, 100)])]),
        Plane("/device:TPU:0", [
            Line("XLA Modules", [Ev("jit_path(5)", 0, 90)]),
            Line("XLA Ops", [op("while.2", 10, 75)]
                 + [op(*leaf) for leaf in leaves])]),
    ])
    tr = trace_reduce.reduce(pd, hlo=trace_reduce.read_hlo_dir(str(tmp_path)))
    assert tr["scopes"] == pytest.approx({
        "svm_path/prologue": 10e-9, "svm_path/screen": 10e-9,
        "svm_path/solve": 30e-9, "svm_path/solve/compact": 15e-9,
        "svm_path/certify": 25e-9, "svm_path/certify/feasibility": 15e-9,
        "other": 10e-9})
    phases = {k: v for k, v in tr["scopes"].items()
              if trace_reduce.phase(k) == k}
    assert sum(phases.values()) == pytest.approx(tr["busy_s"])
    # the breakdown labels an op by its top-level phase
    assert dict(tr["device_ops"]) == pytest.approx({
        "svm_path/prologue:fusion": 10e-9, "svm_path/screen:fusion": 10e-9,
        "svm_path/solve:gather": 10e-9, "svm_path/solve:fusion": 20e-9,
        "svm_path/certify:fusion": 25e-9, "other:copy": 10e-9})


def recorded_trace(tmp_path) -> dict:
    """The reduction of a trace recorded on a TPU v5e (the cell's path at
    2,048 x 1,024, 20 steps, two paths) with the optimized HLO of its scan
    program."""
    import gzip

    from jax.profiler import ProfileData

    fixture = BENCH / "fixtures" / "trace_v5e_tiny"
    for gz in fixture.glob("*.txt.gz"):
        (tmp_path / gz.name[:-3]).write_bytes(gzip.open(gz).read())
    pd = ProfileData.from_serialized_xspace(
        gzip.open(fixture / "trace.xplane.pb.gz").read())
    return trace_reduce.reduce(pd, [0],
                               trace_reduce.read_hlo_dir(str(tmp_path)))


def test_trace_reduce_reads_a_recorded_tpu_trace(tmp_path):
    """Every phase of the path is found, the Pallas sweeps under the
    solve."""
    tr = recorded_trace(tmp_path)
    assert tr["paths"] == 2 and tr["planes"] == 1
    assert 0 < tr["busy_s"] <= tr["window_s"]
    assert set(tr["scopes"]) == {"svm_path/screen", "svm_path/solve",
                                 "svm_path/certify", "other"}
    assert tr["scopes"]["svm_path/solve"] > 0.5 * tr["busy_s"]
    assert sum(tr["scopes"].values()) <= tr["busy_s"] * (1 + 1e-9)
    top = [name for name, _ in tr["device_ops"][:2]]
    assert set(top) == {"svm_path/solve:hinge_margin_pallas",
                        "svm_path/solve:hinge_grad_pallas"}


def test_recorded_trace_keeps_each_phase_to_the_last_digit(tmp_path):
    """The three phases read what the reduction that knew only these three
    scopes read from the same trace, to the last digit."""
    scopes = recorded_trace(tmp_path)["scopes"]
    assert scopes["svm_path/screen"] == 0.0005988049999999998
    assert scopes["svm_path/solve"] == 0.032131391000000606
    assert scopes["svm_path/certify"] == 0.005266666000000008


@pytest.mark.parametrize("name,scope", [("certify_ms", "svm_path/certify"),
                                        ("prologue_ms", "svm_path/prologue")])
def test_scope_reader_tells_an_absent_scope_from_an_empty_trace(name, scope):
    reader = run.load_module(run.BENCH / "metrics" / f"{name}.py")

    def read(**trace):
        return reader.read(SimpleNamespace(paths=[], trace=trace or None,
                                           peaks={}))

    assert read() is None
    # no device op in the trace: nothing to read
    assert read(paths=2, planes=0, scopes={}) is None
    # device ops, none under the scope: the program does not open it
    with pytest.raises(LookupError):
        read(paths=2, planes=1, scopes={"other": 1.0})
    assert read(paths=2, planes=1, scopes={scope: 0.5}) == 250.0


@pytest.mark.parametrize("name,key,scale", [
    ("entry_ms", "path.entry_s", 1e3),
    ("feas_binding", "path.certify_binding_share", 100.0),
    ("feas_rounds", "path.certify_rounds_share", 100.0)])
def test_counter_reader_tells_an_absent_counter_from_an_empty_one(
        monkeypatch, name, key, scale):
    from repro.obs import metrics

    reader = run.load_module(run.BENCH / "metrics" / f"{name}.py")
    ran = SimpleNamespace(paths=[{}], trace=None, peaks={})
    monkeypatch.setattr(metrics, "snapshot", lambda: {})
    # no path ran: nothing to read; paths ran: the program has no such
    # histogram
    assert reader.read(None) is None
    assert reader.read(SimpleNamespace(paths=[], trace=None)) is None
    with pytest.raises(LookupError):
        reader.read(ran)
    monkeypatch.setattr(metrics, "snapshot", lambda: {key: {"last": None}})
    assert reader.read(ran) is None
    monkeypatch.setattr(metrics, "snapshot", lambda: {key: {"last": 0.25}})
    assert reader.read(ran) == scale * 0.25


# -- the control and the faults ---------------------------------------------

@pytest.mark.parametrize("config", MID_CONFIGS)
def test_control_is_not_correct(monkeypatch, config):
    """The lower-precision control (``entries/control_high.py``), run by
    ``run.py`` in the program's place on the whole grid of the
    configuration's first cell at its ``mid`` size, comes out not correct;
    the program on the same data comes out correct.

    For ``rcv1`` at this size on the CPU the two separate on ``obj_excess``
    (program 1.1e-6, control 1.5e-4) and not on ``obj_report`` (2.1e-7 and
    4.6e-7), so its ``mid_limits`` limit ``obj_excess`` between those
    readings. At the cell's size on the chip it is the other way round, and
    the workload's limit is on ``obj_report`` (``PERF.md`` section 2)."""
    cell = next(w["name"] for w in BENCHMARK["workloads"]
                if w["config"] == config)
    take = run.read_json(run.BENCH / "workloads" / f"{cell}.json")["grid"][
        "take"]
    limits = cpu_shapes(config).get("mid_limits")
    program = run_cell(monkeypatch, cell, take=take, seed=11, size="mid",
                       limits=limits)
    control = run_cell(monkeypatch, cell, take=take, seed=11, size="mid",
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
             seed=2 ** 32 + 9, size="tiny", limits=None):
    import repro.core

    def read_json(path: Path):
        d = tiny_json(path, size)
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


@pytest.mark.parametrize("counter", ["absent", "empty"])
def test_traced_run_leaves_out_a_metric_the_program_lacks(monkeypatch, capsys,
                                                          counter):
    """A reader that raises ``LookupError`` (the program has no such
    counter) leaves its metric out, and the run ends with a result; one
    that returns ``None`` (the counter is there and holds nothing) stops the
    run."""
    from repro.obs import metrics

    listed = [m for m in BENCHMARK["per_layer"]
              if m["name"] in ("entry_ms", "kept_share")]
    monkeypatch.setattr(run, "per_layer_metrics", lambda bench, cell: listed)
    snapshot = {} if counter == "absent" else {"path.entry_s": {"last": None}}
    monkeypatch.setattr(metrics, "snapshot", lambda: snapshot)
    # a traced run points XLA's dumps at a directory of its own
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    if counter == "empty":
        with pytest.raises(run.Refusal, match="entry_ms found nothing"):
            run_cell(monkeypatch, RUN_CELLS[0], trace=1)
        return
    out = run_cell(monkeypatch, RUN_CELLS[0], trace=1)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"kept_share"}
    assert "[metric] entry_ms absent from this program" in (
        capsys.readouterr().out)


# -- a configuration added as files alone ------------------------------------

GAUSS_GEN = '''"""Dense Gaussian binary problem: unit-norm samples, labels from a
planted direction on the first ``support`` features with Gaussian noise,
split at the median."""

import jax
import jax.numpy as jnp


def generate(key, shape, params):
    m, n = shape["features"], shape["samples"]
    k_x, k_w, k_n = jax.random.split(key, 3)
    X = jax.random.normal(k_x, (m, n), jnp.float32)
    X = X / jnp.linalg.norm(X, axis=0, keepdims=True)
    w = jax.random.normal(k_w, (m,)) * (jnp.arange(m) < params["support"])
    s = jnp.matmul(w, X, precision=jax.lax.Precision.HIGHEST)
    s = s + params["label_noise"] * jnp.std(s) * jax.random.normal(k_n, (n,))
    return X, jnp.where(s >= jnp.median(s), 1.0, -1.0).astype(jnp.float32)
'''

#: the reader of a counter that the program under test does not have
ABSENT_READER = '''"""Iterations solved on every padded row, last traced path."""


def read(run):
    from repro.obs import metrics

    h = metrics.snapshot().get("path.overflow_iters")
    if h is None and run.paths:
        raise LookupError("the program has no histogram path.overflow_iters")
    return None if h is None or h["last"] is None else float(h["last"])
'''

#: drives one traced run of a cell at its CPU size, from a checkout's root
TRACED_RUN = '''import json, sys
sys.path.insert(0, "bench")
import run, test_bench
run.read_json = test_bench.tiny_json
args = run.parse(["--workload", sys.argv[1], "--seed", "5", "--seconds",
                  "0.5", "--trace", "1"])
print(json.dumps(run.run(args, chip_check=test_bench.cpu_chip)))
'''


def test_new_configuration_needs_no_edit_to_a_file_there(tmp_path):
    """A configuration, its cell and a listed counter metric that the
    program lacks, added to a copy of the benchmark as new files and new
    entries of ``BENCHMARK.json`` alone: the copy's own tests cover the
    configuration and its cell from its files, its sound runs are correct
    and its broken ones are not, and a traced run leaves the metric out.
    No file that was there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(run.ROOT / "BENCHMARK.json", root)
    there = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "src").symlink_to(run.ROOT / "src")

    cell = "gauss.glmnet8"
    added = {
        "gen/gauss_dense.py": GAUSS_GEN,
        "metrics/overflow_iters.py": ABSENT_READER,
        "configs/gauss.json": json.dumps({
            "name": "gauss", "generator": "gauss_dense",
            "shape": {"features": 4000, "samples": 2000},
            "generator_params": {"support": 20, "label_noise": 0.1},
            "cpu_shapes": {"tiny": {"features": 200, "samples": 400}}}),
        f"workloads/{cell}.json": json.dumps({
            **run.read_json(BENCH / "workloads" / f"{RUN_CELLS[0]}.json"),
            "name": cell, "config": "gauss"}),
    }
    for rel, text in added.items():
        (root / "bench" / rel).write_text(text)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({**BENCHMARK["configs"][0], "name": "gauss",
                             "file": "bench/configs/gauss.json"})
    bench["workloads"].append({**BENCHMARK["workloads"][0], "name": cell,
                               "config": "gauss", "traffic": "glmnet8"})
    bench["per_layer"].append({
        "name": "overflow_iters", "unit": "iters", "better": "lower",
        "source": "program_counter", "layer": "solver", "moves": "path_s",
        "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    tests = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-k", "gauss", "bench/test_bench.py"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert tests.returncode == 0, tests.stdout[-4000:]
    passed = {ln.split("::", 1)[1].split(" ", 1)[0]
              for ln in tests.stdout.splitlines() if " PASSED" in ln}
    assert passed == {
        "test_generator_is_seeded_and_unit_norm[gauss]",
        "test_reference_certifies_its_own_path[gauss]",
        f"test_sound_run_is_correct[{cell}]",
        *(f"test_broken_path_is_not_correct[{cell}-{f}]" for f in FAULTS)}

    traced = subprocess.run(
        [sys.executable, "-c", TRACED_RUN, cell], cwd=root, env=env,
        capture_output=True, text=True, timeout=600)
    assert traced.returncode == 0, traced.stderr[-4000:]
    out = json.loads(traced.stdout.splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["metrics"] == {}
    assert "[metric] overflow_iters absent from this program" in traced.stdout

    for p, content in there.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == content, p
    old = json.loads(there[root / "BENCHMARK.json"])
    for key, value in old.items():
        if isinstance(value, list):
            assert bench[key][:len(value)] == value, key
        else:
            assert bench[key] == value, key


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
