"""Device busy time, op classes, exposed collectives and idle gaps from a
profiler trace.

``Tracer`` records a window with JAX's profiler (Python tracing off) under
a host span named ``bench.window``, inside the checkout, and deletes the
trace once it is reduced. ``reduce_file`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``:

- a device is a plane named ``/device:<KIND>:<n>`` (TPU, GPU); its ops are
  the events of its ``XLA Ops`` line, each named by its HLO instruction
  (``%name = type opcode(operands), kind=..., calls=...``);
- busy time is the union of a device's op intervals inside the window,
  averaged over the devices; idle is the rest of the window;
- a ``while``, ``conditional`` or ``call`` op contains the ops of its body
  on the same line: it counts for busy time and for no class;
- an op is a collective when its opcode is one (all-gather, all-reduce,
  reduce-scatter, collective-permute, all-to-all, send, recv, with the
  ``-start``/``-done`` halves of async ones) or it is a fusion that calls
  one; a matmul when its opcode is ``convolution`` or ``dot``, it is an
  output fusion (``kind=kOutput``, XLA's fusion around a matmul) or a
  Pallas kernel (``tpu_custom_call``); every other op is other compute;
- exposed collective time is the time in which a collective op runs on a
  device and no other op does;
- an idle gap is labelled ``<bench span>: <host event>`` by what the host
  was doing (see ``_label_gaps``).

Keep a traced window short (a few seconds or steps): the profiler's device
buffer holds some tens of thousands of ops and cuts the trace beyond
them, which would read as idle time."""
from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Sequence, Tuple

import jax

WINDOW = "bench.window"
STEP_PREFIX = "bench."
Interval = Tuple[float, float]


class Tracer:
    """``with Tracer(dir) as t: ...`` traces the block as the window;
    ``t.reduce()`` then returns the reduction and removes the trace."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self._span = None

    def start(self):
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()

    def stop(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def reduce(self) -> Dict[str, Any]:
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no trace under {self.out_dir}")
        try:
            return reduce_file(paths[0])
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def parse_op(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, fusion kind)`` of an HLO instruction's text."""
    if not text.startswith("%") or " = " not in text:
        return text, text, ""
    name, rest = text[1:].split(" = ", 1)
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    rest = rest[i:].lstrip()
    opcode = rest[:rest.find("(")] if "(" in rest else rest
    kind = ""
    m = re.search(r"kind=(k\w+)", text)
    if m:
        kind = m.group(1)
    return name, opcode, kind


COLLECTIVE_OPS = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                            r"collective-permute|all-to-all|send|recv)"
                            r"(-start|-done)?$")
CONTAINERS = {"while", "conditional", "call"}


def op_class(text: str) -> str:
    """``collective``, ``matmul``, ``container`` or ``other``."""
    _, opcode, kind = parse_op(text)
    if opcode in CONTAINERS:
        return "container"
    if COLLECTIVE_OPS.match(opcode) or (
            opcode == "fusion" and re.search(
                r"calls=%(all-gather|all-reduce|reduce-scatter|"
                r"collective-permute|all-to-all)", text)):
        return "collective"
    if opcode in ("convolution", "dot") or kind == "kOutput" or (
            opcode == "custom-call" and "tpu_custom_call" in text):
        return "matmul"
    return "other"


def short_name(text: str) -> str:
    name, opcode, kind = parse_op(text)
    return f"{name} {opcode}" + (f" {kind}" if kind else "")


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_profile(_load(path))


def reduce_profile(pd) -> Dict[str, Any]:
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[str, List[Tuple[float, float, str]]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append((ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                ev.name))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                       for ev in line.events if ev.duration_ns > 0]
                host.append((line.name, evs))
    windows = [(a, b) for _, evs in host for a, b, n in evs if n == WINDOW]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    lo, hi = windows[0]
    window_s = hi - lo
    spans = [(a, b, n) for _, evs in host for a, b, n in evs
             if n.startswith(STEP_PREFIX) and n != WINDOW and a >= lo
             and a < hi]
    steps = defaultdict(int)
    for _, _, n in spans:
        steps[n] += 1
    if not devices:
        return {"window_s": window_s, "devices": 0, "busy_s": 0.0,
                "steps": dict(steps), "op_s": {}, "exposed_collective_s": 0.0,
                "device_ops": [], "idle_gaps": []}

    busy, op_s, exposed = [], defaultdict(float), []
    by_name = defaultdict(float)
    gaps_all = []
    for dev, ops in sorted(devices.items()):
        ops = [(max(a, lo), min(b, hi), n) for a, b, n in ops
               if min(b, hi) > max(a, lo)]
        classes = defaultdict(list)
        for a, b, n in ops:
            k = op_class(n)
            classes[k].append((a, b))
            if k != "container":
                by_name[short_name(n)] += b - a
        every = union(iv for ivs in classes.values() for iv in ivs)
        busy.append(total(every))
        for k, ivs in classes.items():
            if k != "container":
                op_s[k] += total(union(ivs))
        compute = union(classes["matmul"] + classes["other"])
        exposed.append(total(subtract(union(classes["collective"]),
                                      compute)))
        gaps_all.append(subtract([(lo, hi)], every))
    n_dev = len(busy)
    labelled = _label_gaps(gaps_all[0], host, spans)
    top = sorted(((n, s / n_dev) for n, s in by_name.items()),
                 key=lambda x: -x[1])[:10]
    return {
        "window_s": window_s,
        "devices": n_dev,
        "busy_s": sum(busy) / n_dev,
        "steps": dict(steps),
        "op_s": {k: v / n_dev for k, v in op_s.items()},
        "exposed_collective_s": sum(exposed) / n_dev,
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": labelled[:10],
    }


def _label_gaps(gaps: List[Interval], host, spans) -> List[List[Any]]:
    """The longest idle gaps of one device, each named by what the host
    was doing: ``<bench span>: <host event>``, the bench span and the
    other event of the bench's thread that cover most of the gap, and at
    least half of it (``-`` where none does: for a host event, Python
    between calls into JAX)."""
    threads = {line for line, evs in host
               if any(n.startswith(STEP_PREFIX) for _, _, n in evs)}
    events = [(a, b, n) for line, evs in host if line in threads
              for a, b, n in evs if not n.startswith(STEP_PREFIX)]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        names = []
        for pool in (spans, events):
            best, label = 0.5 * (b - a), "-"
            for s, e, n in pool:
                ov = min(b, e) - max(a, s)
                if ov > best:
                    best, label = ov, n
            names.append(label)
        out.append([f"{names[0]}: {names[1]}", b - a])
    return out
