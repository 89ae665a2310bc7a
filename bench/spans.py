"""Device time by the program's own names, and the trainer's host phases,
from a profiler trace and the compiled step's HLO text.

The program names its work where it happens: ``dataflow.execute`` runs
every graph node under ``jax.named_scope("df.<op>.<node>")``,
``tp.sp_period`` its forward and backward ``shard_map`` bodies under
``tp.period``, the model its embedding and head under ``model.embed`` and
``model.head``, the train step AdamW under ``step.optimizer``. Those
scopes reach the compiled HLO as each instruction's ``op_name`` metadata.
``Trainer.run`` marks each step's host phases with profiler spans:
``train.batch``, ``train.dispatch`` and ``train.sync``, which do not
overlap.

``reduce_profile(pd, hlo_text)`` joins the trace's device ops to the text
by instruction name (an op event is named by its instruction,
``%name = ...``, see ``bench/trace.py``), inside the traced window:

- an op's label is the innermost ``df.<op>`` scope in its ``op_name``,
  else the innermost of ``SCOPES``, else ``unscoped``; an op whose name
  the text does not hold, or that runs outside the step's module, is
  ``unresolved``; its phase is ``remat`` (a rematerialised replay),
  ``bwd`` (under ``transpose(...)``) or ``fwd``;
- per chip: the union of op time of each label (a ``while``,
  ``conditional`` or ``call`` op adds the time that none of its body's
  ops covers, under its own label), its collective time, and the part of
  that with no compute op running; then the mean over chips;
- ``graph_exposed_s``: time in which a collective labelled ``df.*`` runs
  and no compute op does; ``outside_graph_s``: the union of every op
  labelled other than ``df.*``;
- host: the ``train.*`` spans in the window; ``steps`` counts
  ``train.dispatch`` spans, a step's gap runs from its ``train.sync`` end
  to the next ``train.dispatch`` start.

Times are seconds over the whole window, as in ``bench/trace.py``."""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from bench.trace import WINDOW, op_class, parse_op, short_name, subtract, \
    total, union

SCOPES = ("tp.period", "model.embed", "model.head", "step.optimizer")
PHASES = ("train.batch", "train.dispatch", "train.sync")
UNRESOLVED = "unresolved"
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*?\bop_name=\"([^\"]*)\"")
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


def scope_map(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module name, {instruction name: op_name})`` of a compiled
    module's text; an instruction without metadata maps to ``""``."""
    module = ""
    names: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            names[m.group(1)] = op.group(1) if op else ""
    return module, names


def label_of(op_name: str) -> Tuple[str, str, str]:
    """``(label, node, phase)`` of an instruction's ``op_name``: the
    innermost ``df.<op>`` scope (label ``df.<op>``, node the rest) or
    program scope, else ``unscoped``; a scope transformed by JAX reads
    ``jvp(model.head)`` and the like."""
    if "rematted_computation" in op_name:
        phase = "remat"
    elif "transpose(" in op_name:
        phase = "bwd"
    else:
        phase = "fwd"
    segs = []
    for s in op_name.split("/"):
        m = _WRAPPED.match(s)
        segs.append(m.group(1) if m else s)
    for s in reversed(segs):
        if s.startswith("df.") and len(s) > 3:
            op, _, node = s[3:].partition(".")
            return "df." + op, node, phase
    for s in reversed(segs):
        if s in SCOPES:
            return s, "", phase
    return UNSCOPED, "", phase


def _host_phases(host_events, lo: float, hi: float) -> Dict[str, Any]:
    evs = sorted((a, b, n) for a, b, n in host_events
                 if n in PHASES and lo <= a < hi)
    phase_s = defaultdict(float)
    for a, b, n in evs:
        phase_s[n] += b - a
    dispatch = [(a, b) for a, b, n in evs if n == "train.dispatch"]
    syncs = [(a, b) for a, b, n in evs if n == "train.sync"]
    gaps = []
    for prev, (d0, _) in zip(dispatch, dispatch[1:]):
        ends = [b for a, b in syncs if a >= prev[1] and b <= d0]
        if ends:
            gaps.append(d0 - max(ends))
    batches = [a for a, b, n in evs if n == "train.batch"]
    step_s = []
    for a, b in syncs:
        starts = [s for s in batches if s <= a]
        if starts:
            step_s.append(b - max(starts))
    return {"steps": len(dispatch), "gap_s": gaps, "step_s": step_s,
            "phase_s": {n: phase_s[n] for n in PHASES}}


def _device(ops, modules, module, names, lo, hi, acc) -> None:
    """Add one chip's label times to ``acc``."""
    mods = [(a, b) for a, b, n in modules
            if n == module or n.startswith(module + "(")]

    def in_step(a, b):
        m = 0.5 * (a + b)
        return not modules or any(s <= m <= e for s, e in mods)

    leaf = defaultdict(list)
    by_phase = defaultdict(list)
    coll = defaultdict(list)
    compute, conts = [], []
    for a, b, text in ops:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        name = parse_op(text)[0]
        op_name = names.get(name) if in_step(a, b) else None
        if op_name is None:
            lab, node, phase = UNRESOLVED, "", ""
            acc["unresolved_ops"][short_name(text)] += b - a
        else:
            lab, node, phase = label_of(op_name)
        cls = op_class(text)
        if cls == "container":
            conts.append((a, b, lab, phase))
            continue
        leaf[lab].append((a, b))
        if phase:
            by_phase[(lab, phase)].append((a, b))
        if node:
            acc["nodes"][lab].add(node)
        (coll[lab] if cls == "collective" else compute).append((a, b))
    covered = union(iv for ivs in leaf.values() for iv in ivs)
    every = union(covered + [(a, b) for a, b, _, _ in conts])
    for a, b in subtract(union((a, b) for a, b, _, _ in conts), covered):
        # time only a container covers: its innermost one's label
        around = [c for c in conts if c[0] <= a and b <= c[1]] or \
            [c for c in conts if c[0] < b and a < c[1]]
        _, _, lab, phase = min(around, key=lambda c: c[1] - c[0])
        leaf[lab].append((a, b))
        if phase:
            by_phase[(lab, phase)].append((a, b))
    compute = union(compute)
    acc["busy_s"] += total(every)
    for lab, ivs in leaf.items():
        acc["s"][lab] += total(union(ivs))
    for (lab, phase), ivs in by_phase.items():
        acc["phase_s"][lab][phase] += total(union(ivs))
    for lab, ivs in coll.items():
        ivs = union(ivs)
        acc["collective_s"][lab] += total(ivs)
        acc["exposed_s"][lab] += total(subtract(ivs, compute))
    graph_coll = union(iv for lab, ivs in coll.items()
                       if lab.startswith("df.") for iv in ivs)
    acc["graph_exposed_s"] += total(subtract(graph_coll, compute))
    acc["outside_graph_s"] += total(union(
        iv for lab, ivs in leaf.items() if not lab.startswith("df.")
        for iv in ivs))


def reduce_profile(pd, hlo_text: str) -> Dict[str, Any]:
    module, names = scope_map(hlo_text)
    devices: Dict[str, Tuple[list, list]] = {}
    host_events: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [(ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                       for ev in line.events]
                (ops if line.name == "XLA Ops" else modules).extend(evs)
            if ops:
                devices[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host_events.extend(
                    (ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                    for ev in line.events if ev.duration_ns > 0)
    windows = [(a, b) for a, b, n in host_events if n == WINDOW]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    lo, hi = windows[0]
    host = _host_phases(host_events, lo, hi)
    acc: Dict[str, Any] = {
        "busy_s": 0.0, "graph_exposed_s": 0.0, "outside_graph_s": 0.0,
        "s": defaultdict(float), "collective_s": defaultdict(float),
        "exposed_s": defaultdict(float),
        "phase_s": defaultdict(lambda: defaultdict(float)),
        "nodes": defaultdict(set), "unresolved_ops": defaultdict(float)}
    for _, (ops, modules) in sorted(devices.items()):
        _device(ops, modules, module, names, lo, hi, acc)
    n = max(len(devices), 1)
    labels = {
        lab: {"s": s / n,
              "collective_s": acc["collective_s"].get(lab, 0.0) / n,
              "exposed_s": acc["exposed_s"].get(lab, 0.0) / n,
              "nodes": len(acc["nodes"].get(lab, ())),
              "phase_s": {p: v / n
                          for p, v in sorted(acc["phase_s"][lab].items())}}
        for lab, s in sorted(acc["s"].items())}
    top = sorted(acc["unresolved_ops"].items(), key=lambda x: -x[1])[:5]
    return {
        "module": module,
        "devices": len(devices),
        "window_s": hi - lo,
        "steps": host["steps"],
        "busy_s": acc["busy_s"] / n,
        "labels": labels,
        "unresolved_s": labels.get(UNRESOLVED, {"s": 0.0})["s"],
        "unresolved_ops": [[name, s / n] for name, s in top],
        "graph_exposed_s": acc["graph_exposed_s"] / n,
        "outside_graph_s": acc["outside_graph_s"] / n,
        "host": host,
    }


def reduce_file(path: str, hlo_text: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), hlo_text)


def reduce_dir(trace_dir: str, hlo_text: str) -> Optional[Dict[str, Any]]:
    """``reduce_file`` on the trace ``bench.trace.Tracer`` wrote under
    ``trace_dir`` (None where it wrote none)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return reduce_file(paths[0], hlo_text) if paths else None


def node_table(sp: Dict[str, Any]) -> Optional[Dict[str, Dict[str, Any]]]:
    """The per-label table in ms a step: time, collective time, its
    exposed part, time per phase and node count."""
    steps = sp["steps"] if sp else 0
    if not steps:
        return None
    ms = 1e3 / steps
    return {lab: {"ms": row["s"] * ms,
                  "collective_ms": row["collective_s"] * ms,
                  "exposed_ms": row["exposed_s"] * ms,
                  "phase_ms": {p: v * ms for p, v in row["phase_s"].items()},
                  "nodes": row["nodes"]}
            for lab, row in sp["labels"].items()}


def readings(sp: Optional[Dict[str, Any]], chips: int
             ) -> Dict[str, Optional[float]]:
    """Milliseconds a step, None where there is nothing to read:
    ``graph_exposed_ms_per_step`` (collectives of graph nodes with no
    compute running; a ring's only, so None on one chip),
    ``outside_graph_ms_per_step`` (the ops no graph node runs; likewise)
    and ``host_gap_ms_per_step`` (the mean host gap between steps)."""
    out: Dict[str, Optional[float]] = {
        "graph_exposed_ms_per_step": None,
        "outside_graph_ms_per_step": None,
        "host_gap_ms_per_step": None}
    if not sp:
        return out
    gaps = sp["host"]["gap_s"]
    if gaps:
        out["host_gap_ms_per_step"] = 1e3 * sum(gaps) / len(gaps)
    graph = any(k.startswith("df.") for k in sp["labels"])
    if sp["devices"] and chips > 1 and sp["steps"] and graph:
        ms = 1e3 / sp["steps"]
        out["graph_exposed_ms_per_step"] = sp["graph_exposed_s"] * ms
        out["outside_graph_ms_per_step"] = sp["outside_graph_s"] * ms
    return out
