"""Serving cells: the program's paged ``Engine`` serves the traffic mix's
open-loop arrivals; each request is timed from the moment it was due.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engine, and warms both of its step shapes (a prompt of two
prefill chunks, then decode steps) through ``Engine.run``. The window is
one ``Engine.run`` over every request due in ``seconds``; it ends when the
last of them has its last token. Afterwards a sample of the finished
requests, drawn from the seed and holding the longest, is read by the
reference."""
from __future__ import annotations

import gc
import time
from typing import Any, Dict

import jax
import numpy as np

from bench import flops, program, stats, trace, traffic, weights
from bench.reference import serve as ref_serve


class _Step:
    """The engine's compiled step under a host span, starting and stopping
    the trace at fixed times into the window (traced runs only)."""

    def __init__(self, step, tracer, start_at: float, stop_at: float):
        self.step, self.tracer = step, tracer
        self.start_at, self.stop_at = start_at, stop_at
        self.state = "before"

    def __call__(self, *args):
        now = time.monotonic()
        if self.state == "before" and now >= self.start_at:
            self.tracer.start()
            self.state = "tracing"
        elif self.state == "tracing" and now >= self.stop_at:
            self.tracer.stop()
            self.state = "done"
        with jax.profiler.TraceAnnotation("bench.serve_step"):
            return self.step(*args)


def build(cfg: Dict[str, Any], seed: int):
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    arch = program.arch_of(cfg)
    rt = program.runtime_of(cfg, arch)
    model = build_model(arch, rt)
    key = weights.seed_key(seed)
    layout = jax.eval_shape(model.init, key)
    params = jax.jit(lambda k: weights.make_params(layout, k))(key)
    engine = Engine(model, params, arch, rt,
                    ServeConfig(**cfg["program"]["serve"]),
                    mesh=program.mesh_of(cfg))
    return engine, layout


def warm_up(engine, vocab: int) -> None:
    """Compile both step shapes: a prompt one token longer than a prefill
    chunk feeds two mixed steps, then three tokens decode."""
    from repro.serve import Request

    n = engine.sc.prefill_chunk + 1
    prompt = (np.arange(n) % (vocab - 1) + 1).astype(np.int32)
    engine.run([Request(rid=0, prompt=prompt, max_new_tokens=4)], key=0)


def requests_of(arrivals):
    from repro.serve import Request

    return [Request(rid=a.rid, prompt=a.prompt, max_new_tokens=a.max_new,
                    arrival_time=a.arrival) for a in arrivals]


def sample(reqs, n: int, seed: int):
    """``n`` finished requests drawn from ``seed``, the longest among them."""
    done = [r for r in reqs if r.done]
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out_tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check_rows(tr) -> int:
    rows = tr["check_requests"] * tr["output_len"]["max"]
    return -(-rows // ref_serve.ROW_BLOCK) * ref_serve.ROW_BLOCK


def run(ctx) -> Dict[str, Any]:
    cfg, tr = ctx.config, ctx.traffic
    vocab = cfg["vocab_size"]
    engine, layout = build(cfg, ctx.seed)
    warm_up(engine, vocab)
    reqs = requests_of(traffic.serve_requests(tr, ctx.seed, ctx.seconds,
                                              vocab))
    setup_s = time.monotonic() - ctx.t_start

    tracer = None
    if ctx.trace:
        tracer = trace.Tracer(ctx.trace_dir)
        t = time.monotonic() + 0.3 * ctx.seconds
        engine._step = _Step(engine._step, tracer, t, t + tr["trace_seconds"])
    compiles = ctx.clock.count
    pauses = stats.GcPauses()
    t0 = time.monotonic()
    engine.run(reqs, key=0)
    wall = time.monotonic() - t0
    pauses.close()
    compiles = ctx.clock.count - compiles
    traced = None
    if tracer is not None:
        tracer.stop()
        traced = tracer.reduce()
    report = dict(engine.last_report)
    mem = ctx.memory_peak()

    ttft = stats.ttfts(reqs)
    gaps = stats.inter_token_gaps(reqs)
    done = [r for r in reqs if r.done]
    span = stats.served_span(done)
    out_tokens = sum(len(r.out_tokens) for r in done)
    picked = sample(reqs, tr["check_requests"], ctx.seed)
    served = [(np.asarray(r.prompt, np.int32), list(r.out_tokens))
              for r in picked]

    engine.params = None
    del engine
    gc.collect()
    ref = ref_serve.gaps(cfg, ctx.seed, layout, served,
                         cfg["program"]["serve"]["s_max"],
                         check_rows(tr))
    work = [(len(r.prompt), len(r.out_tokens)) for r in done]
    return {
        "setup_s": setup_s,
        "e2e": {"ttft_p90_ms": stats.nearest_rank(ttft, 90) * 1e3,
                "itl_p95_ms": stats.nearest_rank(gaps, 95) * 1e3},
        "attempted": len(reqs),
        "failed": len(reqs) - len(done),
        "memory_peak_bytes": mem,
        "numbers": {"gap": ref["gap"]},
        "notes": {"compiles_in_window": compiles, "wall_s": wall,
                  "requests": len(reqs), "served_span_s": span,
                  "output_tokens": out_tokens,
                  "checked_tokens": ref["tokens"],
                  "kv_block_utilization": report.get("kv_block_utilization"),
                  "prefix_hits": report.get("prefix_hits"),
                  "ttft_p50_ms": stats.nearest_rank(ttft, 50) * 1e3,
                  "serve_tokens_per_s": out_tokens / span,
                  "itl_p50_ms": stats.nearest_rank(gaps, 50) * 1e3,
                  "itl_max_ms": max(gaps) * 1e3,
                  "gc_in_window": pauses.summary()},
        "record": {
            "kind": "serve", "chips": ctx.chips, "peaks": ctx.peaks,
            "window_s": span, "flops": flops.serve_flops(cfg, work),
            "tokens_per_step": stats.tokens_per_step(done),
            "trace": traced,
        },
    }
