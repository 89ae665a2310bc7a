"""The reference's readings of a training job: the same weights (made
again from the seed), the same batches, the loss of each checked step, the
first clipped gradient and the parameters' change, in float32 at the
highest matmul precision, with its own AdamW.

On several chips the reference's arrays are spread over all of them, each
on its largest axis that divides, and the batch by rows; the compiler
partitions the plain code."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import check, traffic, weights
from bench.reference import model as ref


def _mesh() -> Optional[Mesh]:
    devs = jax.devices()
    return Mesh(np.array(devs), ("x",)) if len(devs) > 1 else None


def _spread(mesh: Optional[Mesh], shape) -> Optional[NamedSharding]:
    if mesh is None:
        return None
    n = mesh.devices.size
    axes = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in axes:
        if shape[i] % n == 0 and shape[i] >= n:
            spec = [None] * len(shape)
            spec[i] = "x"
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def readings(cfg: Dict[str, Any], job: Dict[str, Any], seed: int, layout,
             steps: int, quant: bool = False,
             drop_half: bool = False) -> Dict[str, Any]:
    """``quant`` computes every matmul in float8 (the control);
    ``drop_half`` leaves the second half of each batch's tokens out of the
    loss (a planted fault)."""
    dm = ref.dims_of(cfg)
    opt = cfg["program"]["optimizer"]
    mesh = _mesh()
    psh = jax.tree.map(lambda l: _spread(mesh, l.shape), layout)
    key = weights.seed_key(seed)
    make = jax.jit(lambda k: weights.make_params(layout, k),
                   out_shardings=psh)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda l: jnp.zeros(l.shape, jnp.float32), layout), out_shardings=psh)
    rows = NamedSharding(mesh, P("x")) if (
        mesh is not None and job["batch"] % mesh.devices.size == 0) else None

    def grad_step(params, tokens, labels, w):
        f = lambda p: ref.loss(p, tokens, labels, w, dm, quant)
        loss, g = jax.value_and_grad(f)(params)
        return loss, ref.clip(g, opt["clip_norm"])

    grad_step = jax.jit(grad_step) if mesh is None else jax.jit(
        grad_step, out_shardings=(NamedSharding(mesh, P()), psh))

    update = jax.jit(lambda p, g, m, v, t, lr: ref.adamw(p, g, m, v, t, lr,
                                                         opt),
                     donate_argnums=(0, 2, 3))
    norms = jax.jit(check.leaf_norms)
    diff_norms = jax.jit(lambda a, b: check.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))

    params, m, v = make(key), zeros(), zeros()
    losses, grad = [], None
    for t in range(steps):
        toks = traffic.train_tokens(dm.vocab, job["batch"], job["seq_len"],
                                    seed, t, job["zipf_alpha"])
        w = np.ones(toks[:, 1:].shape, np.float32)
        if drop_half:
            w.reshape(-1)[w.size // 2:] = 0.0
        args = [jax.device_put(a, rows) for a in
                (toks[:, :-1], toks[:, 1:], w)]
        loss, g = grad_step(params, *args)
        if t == 0:
            grad = check.to_host(norms(g))
        lr = ref.cosine_lr(t, job["lr"], job["lr_warmup"], job["lr_total"])
        params, m, v = update(params, g, m, v, jnp.int32(t), jnp.float32(lr))
        losses.append(float(loss))
        del g
    del m, v
    change = check.to_host(diff_norms(params, make(key)))
    return {"loss": losses, "grad": grad, "update": change}
