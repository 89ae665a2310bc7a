"""train_step / serve-step builders: the jit-compiled units the launcher,
dry-run, trainer, and benchmarks all share.

`make_train_step(model, opt, rt)` returns `(state, batch) -> (state,
metrics)` with optional microbatch gradient accumulation (a `lax.scan` over
microbatches — constant memory at any global batch). State pytree:
{"params", "opt", "step"}.

Gradients flow through `jax.value_and_grad` as usual; when
``rt.tp.graph_backward`` is on (the default) the dense-period portion of
that backward is NOT plain autodiff — ``sp_period`` carries a custom VJP
whose backward is itself a dataflow graph lowered through ``optimize() →
execute()`` (docs/training.md), so pass 3 can pair forward and backward
collectives across microbatch chains. This composes with ``rt.remat``:
``jax.checkpoint`` replays the period forward and then invokes the same
graph-built backward.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.optim.optimizers import Optimizer, global_norm
from repro.runtime import Runtime

State = Dict[str, Any]


def init_state(model, opt: Optimizer, key) -> State:
    params = model.init(key)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(model, opt: Optimizer, rt: Runtime,
                    microbatches: int = 1):
    """Build the jit-able train step (grad accumulation over microbatches)."""

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn)(params, batch)

    def train_step(state: State, batch) -> Tuple[State, Dict[str, Any]]:
        params = state["params"]
        if microbatches == 1:
            loss, grads = grads_of(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape(microbatches, b // microbatches,
                                 *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                acc_loss, acc_grads = carry
                l, g = grads_of(params, mb)
                return (acc_loss + l,
                        jax.tree.map(jnp.add, acc_grads, g)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.float32(0.0), zeros), micro)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)

        with jax.named_scope("step.optimizer"):
            new_params, new_opt = opt.apply(params, grads, state["opt"],
                                            state["step"])
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": global_norm(grads),
            "step": state["step"],
        }
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return train_step


def make_prefill_step(model, rt: Runtime, s_max: Optional[int] = None):
    def prefill_step(params, batch):
        return model.prefill(params, batch, s_max=s_max)
    return prefill_step


def make_decode_step(model, rt: Runtime):
    def decode_step(params, token, caches, idx):
        return model.decode_step(params, token, caches, idx)
    return decode_step
