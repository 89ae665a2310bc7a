"""Inputs made from ``--seed``: serving requests and training tokens.

Serving: the traffic file fixes, through its ``base_seed``, one multiset of
inter-arrival gaps, prompt lengths and output lengths; ``--seed`` only
deals them out in another order and draws the prompts' tokens. So every
seed offers the same work in the same window, and seeds differ in order
and content alone.

Training: tokens follow the program's synthetic pipeline
(``repro.data.pipeline``), copied here so the reference reads the same
batches without calling the program: zipf-distributed ranks from
``PCG64(seed * 1_000_003 + step)``, taken modulo the vocabulary."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


@dataclass
class Arrival:
    rid: int
    arrival: float          # seconds after the window opens
    prompt: np.ndarray      # (prompt_len,) int32, ids in [1, vocab)
    max_new: int


def _lognormal_lengths(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_gaps(traffic: Dict[str, Any], seconds: float) -> np.ndarray:
    """The fixed gaps of a window: Poisson gaps from ``base_seed`` while
    their sum stays under ``seconds`` (the first request comes at 0)."""
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    rng = np.random.default_rng(traffic["base_seed"])
    gaps: List[float] = []
    total = 0.0
    while True:
        g = float(rng.exponential(1.0 / traffic["rate"]))
        if total + g >= seconds:
            return np.asarray(gaps)
        gaps.append(g)
        total += g


def serve_requests(traffic: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[Arrival]:
    gaps = arrival_gaps(traffic, seconds)
    n = len(gaps) + 1
    base = np.random.default_rng(traffic["base_seed"] + 1)
    plens = _lognormal_lengths(base, traffic["prompt_len"], n)
    olens = _lognormal_lengths(base, traffic["output_len"], n)
    rng = np.random.default_rng(seed)
    arrivals = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))])
    plens, olens = rng.permutation(plens), rng.permutation(olens)
    return [Arrival(rid=i, arrival=float(arrivals[i]),
                    prompt=rng.integers(1, vocab, int(plens[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new=int(olens[i]))
            for i in range(n)]


def train_tokens(vocab: int, batch: int, seq: int, seed: int, step: int,
                 zipf_alpha: float) -> np.ndarray:
    """(batch, seq + 1) int32 tokens of one step; inputs are ``[:, :-1]``
    and labels ``[:, 1:]``."""
    rng = np.random.default_rng(np.random.PCG64(seed * 1_000_003 + step))
    ranks = rng.zipf(zipf_alpha, size=(batch, seq + 1)).astype(np.int64)
    return (ranks % vocab).astype(np.int32)
