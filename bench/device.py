"""The chip a run is on: its description, its published peaks, its memory.

Peaks come from ``peaks.json``, keyed by JAX's ``device_kind``. A device
that is not in the table is an error, never a default, and a run that finds
no accelerator, or fewer chips than its cell asks for, stops before it
prints a result."""
from __future__ import annotations

import json
import pathlib
from typing import Any, Dict, List

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """The process sees no accelerator fit to run the cell."""


def peaks_for(device_kind: str) -> Dict[str, Any]:
    with open(PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} has no entry in "
                       f"{PEAKS.name}; add its published peaks with a source")
    return table[device_kind]


def require_chips(devices: List[Any], chips: int) -> Dict[str, Any]:
    """Check that ``devices`` are accelerators of a known kind, at least
    ``chips`` of them; return their peaks."""
    if not devices or devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator; the benchmark runs on the "
                     "chip or not at all")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    try:
        return peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e


def describe(devices: List[Any]) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices: List[Any]) -> int:
    """Peak bytes in use on the fullest of ``devices``, 0 where the
    backend keeps no count."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
