"""Cost extraction from compiled/lowered artifacts.

* ``cost_summary(compiled)``   — FLOPs / bytes-accessed from cost_analysis()
  (per-device numbers: XLA analyzes the partitioned per-device module).
* ``collective_counts(hlo)``   — collective ops of each kind, as the TPU
  compiler emits them (sync, async ``-start``, fused).
* ``collective_bytes(hlo)``    — per-device wire bytes, parsed from the HLO
  text: for every all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute op, sum the operand sizes (cost_analysis does not
  account collectives — the assignment's method).
* ``roofline_terms(...)``      — the three-term roofline per DESIGN/spec:
      compute    = flops_dev / peak_flops
      memory     = bytes_dev / hbm_bw
      collective = coll_bytes_dev / (ici_links × link_bw)
  (per-device numerators ≡ the global formula divided through by chips).
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, Optional

from repro.hw import HWSpec, V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\([^=]*\)|[a-z0-9]+\[[\d,]*\]\S*)\s*"
    r"([\w\-]+)\((.*)\)", )
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device operand bytes of every collective op, by op kind.

    Handles both sync ops (`all-gather(...)`) and async pairs
    (`all-gather-start` — the `-done` is skipped to avoid double counting).
    """
    defs: Dict[str, int] = {}
    per_op: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}

    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op, args = m.groups()
        defs[name.lstrip("%")] = _shape_bytes(type_str)

    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op, args = m.groups()
        base = None
        for k in COLLECTIVE_OPS:
            if op == k or op == k + "-start":
                base = k
                break
        if base is None or op.endswith("-done"):
            continue
        operands = re.findall(r"%?([\w.\-]+)", args.split("channel_id")[0])
        tot = 0
        for o in operands:
            if o in defs:
                tot += defs[o]
        if tot == 0:
            # operands may be inline-typed (older dumps): fall back to the
            # op's own result bytes
            tot = _shape_bytes(type_str)
        per_op[base] += tot
    per_op["total"] = sum(per_op[k] for k in COLLECTIVE_OPS)
    return per_op


# Fused collectives the TPU compiler emits as a fusion that calls a
# computation of this name, by the collective they perform.
_FUSED_COLLECTIVES = {"all-reduce-scatter": "reduce-scatter"}


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Number of collective ops of each kind in a compiled module's text:
    sync ops, the ``-start`` half of async pairs, and the TPU's fused
    collectives (``fusion(...), calls=%all-reduce-scatter``)."""
    counts = {k: len(re.findall(rf"(?<![\w\-]){k}(?:-start)?\(", hlo_text))
              for k in COLLECTIVE_OPS}
    for name, kind in _FUSED_COLLECTIVES.items():
        counts[kind] += len(re.findall(rf"calls=%{name}\b", hlo_text))
    return counts


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def memory_summary(compiled) -> Dict[str, int]:
    ms = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        out[k] = int(getattr(ms, k, 0))
    out["total_hbm_bytes"] = (out["argument_size_in_bytes"]
                              + out["output_size_in_bytes"]
                              + out["temp_size_in_bytes"]
                              - out["alias_size_in_bytes"])
    return out


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_dev: float
    bytes_dev: float
    coll_bytes_dev: float
    dominant: str

    def as_dict(self):
        return asdict(self)


def roofline_terms(flops_dev: float, bytes_dev: float, coll_bytes_dev: float,
                   hw: HWSpec = V5E) -> Roofline:
    compute = flops_dev / hw.peak_flops
    memory = bytes_dev / hw.hbm_bw
    coll = coll_bytes_dev / (hw.ici_links * hw.ici_bw)
    dom = max((("compute", compute), ("memory", memory),
               ("collective", coll)), key=lambda kv: kv[1])[0]
    return Roofline(compute, memory, coll, flops_dev, bytes_dev,
                    coll_bytes_dev, dom)
