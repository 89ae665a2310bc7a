#!/usr/bin/env python3
"""Start the system on a TPU v5e through its own entry points, and check
what comes out.

    python3 chip_smoke.py              # one chip: train + serve
    python3 chip_smoke.py --four-chip  # four chips: the TP ring, nothing else

One process and no children: the chip belongs to one process at a time.
Phases on one chip:

* device: the first device must be a TPU of the kind ``repro.hw`` describes.
  There is no CPU fallback and no interpret mode.
* train: gemma3-1b at published widths, depth cut to one 6-layer period,
  bf16 compute, batch 1 x 4096, 3 steps through the trainer that
  ``repro.launch.train`` builds. Every loss is finite, and step 0's loss
  agrees within ``TRAIN_LOSS_RTOL`` with the same batch's loss in float32
  at ``highest`` matmul precision.
* serve: gemma3-1b at full depth through the paged ``Engine`` that
  ``repro.launch.serve`` builds: 4 greedy requests, prompt 512, 32 new
  tokens. The tokens must equal ``DenseEngine``'s; where a request's tokens
  first differ, the dense model's top-2 logit margin there must be at most
  ``SERVE_MARGIN_TOL`` (a near-tie that bf16 rounding may flip).

With ``--four-chip``: deepseek-7b at published widths, depth cut to 2
layers, on ``data=1 x model=4``, 3 steps each with ``--tp-mode`` barrier,
cais and auto from one seed and batch. Losses agree within
``MODE_LOSS_RTOL``; the compiled cais step carries collective-permutes and
the barrier step all-gathers and reduce-scatters; every state array spans
all 4 devices.

Any failed check raises and the exit code is non-zero. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

import jax
import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
SRC = REPO / "src"

SEED = 0
MAX_NEW = 32
TRAIN_ARGV = ["--arch", "gemma3-1b", "--periods", "1", "--batch", "1",
              "--steps", "3", "--seed", str(SEED)]
SERVE_ARGV = ["--arch", "gemma3-1b", "--requests", "4", "--prompt-len",
              "512", "--max-new", str(MAX_NEW), "--seed", str(SEED)]
RING_ARGV = ["--arch", "deepseek-7b", "--mesh", "host", "--periods", "2",
             "--batch", "4", "--steps", "3", "--seed", str(SEED)]
RING_MODES = ("barrier", "cais", "auto")

# bf16 keeps 8 significand bits (relative rounding 2^-9 per op); a mean
# cross-entropy over 4096 tokens after a few layers stays well inside 1%.
TRAIN_LOSS_RTOL = 1e-2
# The engines block the same math differently (8-token prefill chunks vs
# one 512-token prefill), so in bf16 greedy tokens may flip only at a
# near-tie: where the dense model's two best logits are this close.
SERVE_MARGIN_TOL = 0.1
# The three backends compute the same step with collectives in another
# order; in bf16 that moves the loss by rounding only.
MODE_LOSS_RTOL = 1e-2


class CompileClock:
    """Sums the backend compile seconds JAX reports, so a phase can say how
    much of its time went to compiling (persistent-cache hits add none)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check_device(want_count: int):
    """The first device must be a TPU of the kind ``repro.hw`` describes."""
    from repro.hw import V5E

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        fail(f"JAX found no TPU (platform {d.platform!r}); this script runs "
             "on the chip or not at all")
    if d.device_kind != V5E.device_kind:
        fail(f"device_kind {d.device_kind!r} is not the {V5E.device_kind!r} "
             "that repro.hw describes")
    if len(devs) != want_count:
        fail(f"this phase needs {want_count} chip(s); JAX sees {len(devs)}")
    return d


def train_phase(clock: CompileClock) -> None:
    from repro.data.pipeline import make_batch
    from repro.launch import train
    from repro.models import build_model

    c0 = clock.seconds
    trainer, state = train.build(TRAIN_ARGV)
    cfg = trainer.arch
    print(f"train: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} batch="
          f"{trainer.shape.global_batch}x{trainer.shape.seq_len} "
          f"compute={trainer.rt.compute_dtype}", flush=True)
    trainer.run(state)
    del state
    losses = [h["loss"] for h in trainer.history]
    step_s = [h["dt"] for h in trainer.history]
    print(f"train: losses {losses}")
    print(f"train: step seconds {step_s} (step 0 includes compile)")
    print(f"train: compile seconds {clock.seconds - c0}", flush=True)
    if len(losses) != 3 or not all(math.isfinite(x) for x in losses):
        fail(f"train losses not 3 finite values: {losses}")

    # float32 reference of step 0: same params (same init, same seed), same
    # batch, float32 compute at highest matmul precision
    ref_model = build_model(cfg, dataclasses.replace(
        trainer.rt, compute_dtype="float32"))
    params = trainer.fresh_state(SEED)["params"]
    batch = make_batch(cfg, trainer.shape, 0, trainer.data)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(ref_model.loss)(params, batch))
    del params
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"train: step-0 loss bf16 {losses[0]} float32 {ref} rel diff "
          f"{rel} (tol {TRAIN_LOSS_RTOL})", flush=True)
    if not rel <= TRAIN_LOSS_RTOL:
        fail(f"step-0 loss {losses[0]} vs float32 {ref}: rel diff {rel}")


def top2_margin(eng, tokens) -> float:
    """Top-2 logit margin of the engine's model after ``tokens``."""
    logits, _ = jax.jit(eng.model.prefill)(
        eng.params, np.asarray(tokens, np.int32)[None])
    top = np.sort(np.asarray(logits, np.float32).ravel())[-2:]
    return float(top[1] - top[0])


def serve_phase(clock: CompileClock) -> None:
    from repro.launch import serve
    from repro.serve import paged_supported

    c0 = clock.seconds
    t0 = time.monotonic()
    paged_eng, reqs = serve.main(SERVE_ARGV + ["--engine", "paged"])
    paged_s = time.monotonic() - t0
    if not paged_supported(paged_eng.model, paged_eng.cfg):
        fail(f"{paged_eng.cfg.name} does not take the paged path")
    paged = {r.rid: list(r.out_tokens) for r in reqs}
    prompts = {r.rid: list(r.prompt) for r in reqs}
    for r in reqs:
        if not r.done or len(r.out_tokens) != MAX_NEW:
            fail(f"request {r.rid}: done={r.done} with "
                 f"{len(r.out_tokens)} of {MAX_NEW} tokens")
    del paged_eng, reqs
    print(f"serve: paged engine {len(paged)} requests x {MAX_NEW} tokens in "
          f"{paged_s} s", flush=True)

    t0 = time.monotonic()
    dense_eng, dreqs = serve.main(SERVE_ARGV + ["--engine", "dense"])
    dense_s = time.monotonic() - t0
    print(f"serve: dense engine in {dense_s} s; compile seconds (both) "
          f"{clock.seconds - c0}", flush=True)
    for r in dreqs:
        got, want = paged[r.rid], list(r.out_tokens)
        if got == want:
            print(f"serve: request {r.rid}: paged == dense")
            continue
        t = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
        margin = top2_margin(dense_eng, prompts[r.rid] + want[:t])
        print(f"serve: request {r.rid}: first differs at step {t} "
              f"(paged {got[t]} dense {want[t]}), dense top-2 margin "
              f"{margin} (tol {SERVE_MARGIN_TOL})", flush=True)
        if not margin <= SERVE_MARGIN_TOL:
            fail(f"request {r.rid}: paged and dense diverge at step {t} "
                 f"where the top-2 margin is {margin}")


def ring_phase(clock: CompileClock) -> None:
    from repro import sharding
    from repro.data.pipeline import make_batch
    from repro.launch import train
    from repro.roofline.hlo_costs import collective_counts

    n = len(jax.devices())
    losses = {}
    for mode in RING_MODES:
        c0 = clock.seconds
        trainer, state = train.build(RING_ARGV + ["--tp-mode", mode])
        spread = {len(x.sharding.device_set) for x in jax.tree.leaves(state)}
        if spread != {n}:
            fail(f"{mode}: state arrays span {sorted(spread)} devices, "
                 f"not all {n}")
        batch = make_batch(trainer.arch, trainer.shape, 0, trainer.data)
        with sharding.use_mesh(trainer.mesh):
            hlo = trainer.train_step.lower(state, batch).compile().as_text()
        census = collective_counts(hlo)
        state = trainer.run(state)
        spread = {len(x.sharding.device_set) for x in jax.tree.leaves(state)}
        del state
        losses[mode] = [h["loss"] for h in trainer.history]
        print(f"ring: {mode} mesh={dict(trainer.mesh.shape)} layers="
              f"{trainer.arch.num_layers} losses {losses[mode]} step seconds "
              f"{[h['dt'] for h in trainer.history]} compile seconds "
              f"{clock.seconds - c0} hlo {census} state spans {spread}",
              flush=True)
        if spread != {n}:
            fail(f"{mode}: trained state spans {sorted(spread)} devices")
        if not all(math.isfinite(x) for x in losses[mode]):
            fail(f"{mode}: non-finite losses {losses[mode]}")
        if mode == "cais" and census["collective-permute"] == 0:
            fail("cais step has no collective-permute")
        if mode == "barrier" and not (census["all-gather"]
                                      and census["reduce-scatter"]):
            fail(f"barrier step lacks all-gather/reduce-scatter: {census}")
    ref = losses["auto"]
    for mode in RING_MODES:
        rel = max(abs(a - b) / abs(b) for a, b in zip(losses[mode], ref))
        print(f"ring: {mode} vs auto max rel loss diff {rel} "
              f"(tol {MODE_LOSS_RTOL})")
        if not rel <= MODE_LOSS_RTOL:
            fail(f"{mode} losses {losses[mode]} vs auto {ref}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip TP-ring phase")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not at {SRC / 'repro'}; run this script "
             "from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.launch.cache import init_compile_cache

    dev = check_device(4 if args.four_chip else 1)
    print(f"compile cache: {init_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.four_chip:
        ring_phase(clock)
    else:
        train_phase(clock)
        serve_phase(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
