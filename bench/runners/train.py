"""Training cells: the program's ``Trainer`` drives its compiled step over
the state this file makes, first through the checked steps, then through
the warm-up, then through the window.

Set-up builds one trainer and one state. Its first ``checked_steps`` steps
go through ``Trainer.run``, the window's own call and feed, and are the
steps the reference follows; the readings of the comparison are taken
from that state on the way (the first gradient from AdamW's first moment,
the parameters' change after the checked steps). The window then runs
whole steps until ``seconds`` have passed and counts every token of every
step it ran over all of its time."""
from __future__ import annotations

import gc
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench import check, flops, program, stats, trace, weights
from bench.reference import train as ref_train


def build(cfg: Dict[str, Any], job: Dict[str, Any], seed: int):
    """``(trainer, state, layout, key, shardings)``: the program's trainer
    for the configuration and job, and its state made on the device from
    ``seed`` in one jitted call, placed as the program shards it."""
    from repro import sharding
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig
    from repro.launch import specs
    from repro.models import build_model
    from repro.optim import cosine_schedule, make_optimizer
    from repro.train import Trainer, TrainerConfig

    arch = program.arch_of(cfg)
    rt = program.runtime_of(cfg, arch)
    mesh = program.mesh_of(cfg)
    oc = dict(cfg["program"]["optimizer"])
    name = oc.pop("name")
    opt = make_optimizer(name, cosine_schedule(job["lr"], job["lr_warmup"],
                                               job["lr_total"]), **oc)
    model = build_model(arch, rt)
    shape = ShapeConfig("bench", job["seq_len"], job["batch"], "train")
    trainer = Trainer(model, opt, arch, shape, rt,
                      TrainerConfig(total_steps=0, log_every=10 ** 9),
                      DataConfig(seed=seed, zipf_alpha=job["zipf_alpha"]),
                      mesh=mesh)
    key = weights.seed_key(seed)
    layout = jax.eval_shape(model.init, key)
    shardings = None
    if mesh is not None:
        with sharding.use_mesh(mesh):
            shapes = jax.eval_shape(
                lambda k: fresh_state(trainer, layout, None, k), key)
            shardings = specs.state_shardings(arch, mesh, shapes, rt)
    state = fresh_state(trainer, layout, shardings, key)
    return trainer, state, layout, key, shardings


def fresh_state(trainer, layout, shardings, key):
    """The trainer's state with weights from ``key`` and AdamW's zeros,
    made on the device in one jitted call and placed by ``shardings``."""
    def fresh(k):
        params = weights.make_params(layout, k)
        return {"params": params, "opt": trainer.opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(fresh, out_shardings=shardings)(key)


def _steps_to(trainer, state, n: int):
    """Run the trainer's loop up to (not including) step ``n``."""
    trainer.cfg.total_steps = n
    with jax.profiler.TraceAnnotation("bench.train_step"):
        return trainer.run(state)


def program_readings(trainer, state, layout, key, shardings, cfg, job):
    """Drive the checked steps; return the state and the readings the
    reference is compared with."""
    b1 = cfg["program"]["optimizer"]["b1"]
    state = _steps_to(trainer, state, 1)
    grad = check.to_host(jax.jit(check.leaf_norms)(state["opt"]["m"]))
    grad = {n: g / (1 - b1) for n, g in grad.items()}
    state = _steps_to(trainer, state, job["checked_steps"])
    psh = shardings["params"] if shardings is not None else None

    def change(params, k):
        p0 = weights.make_params(layout, k)
        if psh is not None:
            p0 = jax.lax.with_sharding_constraint(p0, psh)
        return check.leaf_norms(jax.tree.map(jnp.subtract, params, p0))

    update = check.to_host(jax.jit(change)(state["params"], key))
    loss = [h["loss"] for h in trainer.history[:job["checked_steps"]]]
    return state, {"loss": loss, "grad": grad, "update": update}


def collectives(trainer, state, job) -> Dict[str, int]:
    """Collective ops of each kind in the compiled step (an HLO census,
    traced runs only: lowering the step again costs seconds)."""
    from repro.roofline.hlo_costs import collective_counts

    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        state)
    tok = jax.ShapeDtypeStruct((job["batch"], job["seq_len"]), jnp.int32)
    with jax.profiler.TraceAnnotation("bench.census"):
        text = trainer.train_step.lower(
            shapes, {"tokens": tok, "labels": tok}).compile().as_text()
    return collective_counts(text)


def run(ctx) -> Dict[str, Any]:
    cfg, job = ctx.config, ctx.traffic
    trainer, state, layout, key, shardings = build(cfg, job, ctx.seed)
    state, readings = program_readings(trainer, state, layout, key,
                                       shardings, cfg, job)
    state = _steps_to(trainer, state, job["warmup_steps"])
    jax.block_until_ready(state)
    setup_s = time.monotonic() - ctx.t_start

    traced, census = None, None
    if ctx.trace:
        with trace.Tracer(ctx.trace_dir) as tr:
            for _ in range(job["trace_steps"]):
                state = _steps_to(trainer, state,
                                  trainer.cfg.total_steps + 1)
        traced = tr.reduce()
        if ctx.chips > 1:
            census = collectives(trainer, state, job)

    compiles = ctx.clock.count
    pauses = stats.GcPauses()
    steps, slowest = 0, (0.0, 0.0, -1)
    t0 = time.monotonic()
    while True:
        w, c = time.monotonic(), time.process_time()
        state = _steps_to(trainer, state, trainer.cfg.total_steps + 1)
        w, c = time.monotonic() - w, time.process_time() - c
        slowest = max(slowest, (w, c, steps))
        steps += 1
        if time.monotonic() - t0 >= ctx.seconds:
            break
    window_s = time.monotonic() - t0
    pauses.close()
    compiles = ctx.clock.count - compiles
    losses = [h["loss"] for h in trainer.history]
    dts = sorted(h["dt"] for h in trainer.history[-steps:])
    mem = ctx.memory_peak()

    del state, trainer
    gc.collect()
    ref = ref_train.readings(cfg, job, ctx.seed, layout,
                             steps=job["checked_steps"])
    tokens = steps * job["batch"] * job["seq_len"]
    chips = ctx.chips
    return {
        "setup_s": setup_s,
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "attempted": steps,
        "failed": sum(1 for x in losses if not x == x),
        "memory_peak_bytes": mem,
        "numbers": check.train_numbers(readings, ref),
        "notes": {"compiles_in_window": compiles, "window_s": window_s,
                  "steps": steps, "checked_losses": readings["loss"],
                  "reference_losses": ref["loss"],
                  "last_loss": losses[-1], "collectives": census,
                  "step_s": {"min": dts[0], "median": dts[len(dts) // 2],
                             "max": dts[-1]},
                  "slowest_step": {"wall_s": slowest[0], "cpu_s": slowest[1],
                                   "index": slowest[2]},
                  "gc_in_window": pauses.summary()},
        "record": {
            "kind": "train", "chips": chips, "peaks": ctx.peaks,
            "window_s": window_s, "steps": steps, "tokens": tokens,
            "step_flops": flops.train_step_flops(cfg, job["batch"],
                                                 job["seq_len"]),
            "step_bytes": flops.train_matmul_bytes(cfg),
            "trace": traced,
        },
    }
