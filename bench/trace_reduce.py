"""Reduce a JAX profiler trace to device time per scope, busy time and gaps.

Reads the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes, with
``jax.profiler.ProfileData``. The traced window runs from the start of the
benchmark's first ``bench.path`` span to the end of its last span on the
host. Within it, for each device plane of the chips in use:

* busy time is the union of the intervals of the ops on its ``XLA Ops``
  line;
* device time per scope sums the ops with no other op inside them (an op
  that contains others, such as a loop, is counted through its children),
  each under its deepest name scope (:func:`scope_of`) and under every
  scope that holds that one: ``svm_path/solve`` counts the ops of
  ``svm_path/solve/compact`` too. Ops under no scope count as ``other``;
* an idle gap is a stretch of the window in which no op runs; it is named
  by the benchmark span that holds its middle.

A device op in the trace carries its HLO instruction (``%fusion.3 = ...``)
but not its framework name: that comes from the optimized HLO of its
module (the ``XLA Modules`` event around it), as XLA dumps it as text
(``--xla_dump_to``, ``--xla_dump_hlo_as_text``): the instruction's
``op_name``, else the first scope among the instructions it calls. Where
several dumped modules share the name, the one that holds the most of the
instructions the trace ran under that module event is taken.

Busy and per-scope times are averaged over the device planes.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

#: the name scope under which the path engine opens its phases' scopes
ROOT_SCOPE = "svm_path"
#: components of a name stack that JAX adds itself, not the program
_JAX_SEGMENT = re.compile(r"^(?:while|body|cond|closed_call|branch_\d+_fun"
                          r"|.*\(.*)$")
#: the benchmark's own host spans
SPANS = ("bench.path", "bench.between_paths")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|called_computations"
                    r"|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%?([\w.\-]+)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def scope_of(op_name: str) -> str | None:
    """The deepest name scope under :data:`ROOT_SCOPE` in an ``op_name``,
    else ``None``: the components after its last ``svm_path``, less JAX's
    own (``while``, ``body``, ``cond``, ``branch_<i>_fun``, ``jit(...)``,
    ...) and the trailing primitive. Where XLA merged several names
    (``a;b``), the first that has the root scope counts.
    ``.../svm_path/solve/cond/branch_0_fun/svm_path/solve/compact/gather``
    -> ``svm_path/solve/compact``."""
    for name in op_name.split(";"):
        parts = name.split("/")
        if ROOT_SCOPE not in parts:
            continue
        last = len(parts) - 1 - parts[::-1].index(ROOT_SCOPE)
        kept = [p for p in parts[last + 1:-1] if not _JAX_SEGMENT.match(p)]
        if kept:
            return "/".join([ROOT_SCOPE, *kept])
    return None


def enclosing(scope: str) -> list[str]:
    """``svm_path/solve/compact`` -> ``[svm_path/solve,
    svm_path/solve/compact]``: the scope and each one holding it, below
    the root."""
    parts = scope.split("/")
    return ["/".join(parts[:i]) for i in range(2, len(parts) + 1)] \
        if parts[0] == ROOT_SCOPE else [scope]


def phase(scope: str) -> str:
    """The top-level phase of a scope (``svm_path/solve/compact`` ->
    ``svm_path/solve``)."""
    return enclosing(scope)[0]


def module_scopes(hlo_text: str) -> tuple[str, dict, set]:
    """``(module name, {instruction: scope}, instructions)`` of one
    optimized HLO module in text form; the map holds the instructions that
    fall under a scope (:func:`scope_of`): their own ``op_name``'s, else
    the first one among the instructions they call."""
    module = ""
    own, calls, members = {}, {}, defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTRUCTION.match(line)
        if m:
            name = m.group(1)
            op = _OP_NAME.search(line)
            own[name] = scope_of(op.group(1)) if op else None
            calls[name] = [c for g in _CALLS.findall(line)
                           for c in _NAME.findall(g)]
            members[comp].append(name)
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)

    memo = {}

    def of_computation(c, seen):
        for name in members.get(c, ()):
            sc = of_instruction(name, seen)
            if sc:
                return sc
        return None

    def of_instruction(name, seen):
        if name in memo:
            return memo[name]
        sc = own.get(name)
        if sc is None and name not in seen:
            seen = seen | {name}
            for c in calls.get(name, ()):
                sc = of_computation(c, seen)
                if sc:
                    break
        memo[name] = sc
        return sc

    return (module, {n: sc for n in own if (sc := of_instruction(n, set()))},
            set(own))


def read_hlo_dir(hlo_dir: str | None) -> dict:
    """``{module name: [(scopes, instructions), ...]}`` from the optimized
    modules XLA dumped as text under ``hlo_dir`` (:func:`module_scopes`)."""
    out = defaultdict(list)
    if not hlo_dir:
        return out
    for path in sorted(glob.glob(os.path.join(
            hlo_dir, "**", "*after_optimizations.txt"), recursive=True)):
        with open(path) as f:
            module, scopes, names = module_scopes(f.read())
        out[module].append((scopes, names))
    return out


def instruction_of(event_name: str) -> str:
    """``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_of(event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``."""
    return event_name.split("(", 1)[0]


def _scope_maps(ops, hlo: dict) -> dict:
    """``{module event name: {instruction: scope}}`` for the module events
    that ``ops`` (``(start, end, name, module event name)``) ran under."""
    ran = defaultdict(set)
    for op in ops:
        ran[op[3]].add(instruction_of(op[2]))
    maps = {}
    for event, names in ran.items():
        dumps = hlo.get(module_of(event), [])
        maps[event] = max(dumps, key=lambda d: len(d[1] & names))[0] \
            if dumps else {}
    return maps


def _leaves(ops):
    """Ops (sorted by start, longer first on ties) that contain no other."""
    out = []
    for i, op in enumerate(ops):
        j = i + 1
        if j < len(ops) and ops[j][0] < op[1] and ops[j][1] <= op[1]:
            continue
        out.append(op)
    return out


def _union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _module_at(modules, t):
    """The name of the module event in ``modules`` (sorted) that holds
    time ``t``, else ``""``."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and modules[i][1] >= t else ""


def reduce(pd, device_ids=None, hlo=None) -> dict:
    """Reduce a loaded ``ProfileData``. ``device_ids`` limits the device
    planes to those chips (``/device:TPU:<id>``); by default every one.
    ``hlo`` is :func:`read_hlo_dir`'s map of the modules that ran."""
    hlo = hlo or {}
    spans = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev_id = plane.name.rsplit(":", 1)[-1]
            if device_ids is None or dev_id in {str(i) for i in device_ids}:
                device_planes.append(plane)
    spans.sort()
    paths = sum(1 for s in spans if s[2] == "bench.path")
    if not spans:
        return dict(paths=0, window_s=0.0, busy_s=0.0, scopes={},
                    device_ops=[], idle_gaps=[], planes=0)
    w0 = min(s[0] for s in spans)
    w1 = max(s[1] for s in spans)

    scopes = defaultdict(float)
    by_op = defaultdict(float)
    busy = 0.0
    gaps = []
    used = 0
    for plane in device_planes:
        ops, modules = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = sorted((ev.start_ns, ev.end_ns, ev.name)
                                 for ev in line.events)
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    ops.append((s, e, ev.name))
        if not ops:
            continue
        used += 1
        ops = [(s, e, name, _module_at(modules, s)) for s, e, name in ops]
        ops.sort(key=lambda o: (o[0], -o[1]))
        maps = _scope_maps(ops, hlo)
        for s, e, name, module in _leaves(ops):
            sc = maps[module].get(instruction_of(name)) or "other"
            for outer in enclosing(sc):
                scopes[outer] += (e - s) * 1e-9
            by_op[f"{phase(sc)}:{op_label(name)}"] += (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _, _ in ops])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, span_at((a + b) / 2, spans)))
    n = max(used, 1)
    gaps.sort(key=lambda g: -g[0])
    return dict(
        paths=paths,
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy / n,
        scopes={k: v / n for k, v in scopes.items()},
        device_ops=[[k, v / n] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[label, g * 1e-9] for g, label in gaps[:TOP]],
        planes=used,
    )


def op_label(name: str) -> str:
    """A device op's instruction without its instance number
    (``%fusion.12 = ...`` -> ``fusion``)."""
    return instruction_of(name).split(".")[0]


def span_at(t: float, spans) -> str:
    """The innermost benchmark span holding time ``t``, else ``outside``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside"


def scope_ms(trace: dict | None, scope: str) -> float | None:
    """Device milliseconds per traced path under ``scope``, for a metric's
    reader: ``None`` where the trace holds no device op to read, and
    ``LookupError`` where device ops ran but none under ``scope`` (the
    program does not open it)."""
    if trace is None or trace["paths"] == 0 or trace["planes"] == 0:
        return None
    if scope not in trace["scopes"]:
        raise LookupError(f"no device op ran under {scope}")
    return 1e3 * trace["scopes"][scope] / trace["paths"]


def reduce_dir(trace_dir: str, devices=None, hlo_dir=None) -> dict:
    """:func:`reduce` of the trace under ``trace_dir``; ``devices`` are the
    JAX devices in use, ``hlo_dir`` where XLA dumped their modules."""
    from jax.profiler import ProfileData

    ids = None if devices is None else [d.id for d in devices]
    return reduce(ProfileData.from_file(find_xplane(trace_dir)), ids,
                  read_hlo_dir(hlo_dir))
