"""Latency arithmetic over the requests of a serving window.

A percentile is taken over every request that arrived in the window; one
that failed or never finished counts as missing, which lies above every
measured value. Percentiles are by nearest rank, so a tail is a value that
some request saw."""
from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

MISSING = math.inf


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 < q <= 100) of ``values`` by nearest rank;
    ``MISSING`` entries sort last."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def ttfts(requests: Iterable[Any]) -> List[float]:
    """Seconds from each request's due time to its first token."""
    return [r.t_first_token - r.arrival_time
            if r.done and r.t_first_token is not None else MISSING
            for r in requests]


def inter_token_gaps(requests: Iterable[Any]) -> List[float]:
    """Every gap between consecutive output tokens of every request."""
    gaps: List[float] = []
    for r in requests:
        ts = r.token_times
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps


def tokens_per_step(requests: Iterable[Any]) -> Optional[float]:
    """Mean tokens per token-yielding engine step. The engine stamps every
    token of one step with the same time, so a step is a distinct stamp."""
    per_stamp: Dict[float, int] = {}
    for r in requests:
        for t in r.token_times:
            per_stamp[t] = per_stamp.get(t, 0) + 1
    if not per_stamp:
        return None
    return sum(per_stamp.values()) / len(per_stamp)


def served_span(requests: Sequence[Any]) -> float:
    """Seconds from the first arrival to the last token."""
    first = min(r.arrival_time for r in requests)
    last = max(max(r.token_times) for r in requests if r.token_times)
    return last - first


class GcPauses:
    """Counts the interpreter's garbage collections while open, with their
    seconds and the longest, so a stall in a window can be told apart from
    one of the device or of the host's scheduler."""

    def __init__(self):
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            dt = time.monotonic() - self._t
            self.count += 1
            self.seconds += dt
            self.longest = max(self.longest, dt)

    def close(self):
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)

    def summary(self):
        return {"count": self.count, "seconds": self.seconds,
                "longest_s": self.longest}
