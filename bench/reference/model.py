"""A plain float32 decoder: RMSNorm, rotary embeddings, grouped-query
attention, a SwiGLU feed-forward and the LM head, with its cross-entropy
loss and AdamW. Written from the published LLaMA-style description
(DeepSeek LLM, arXiv:2401.02954; InternLM2, arXiv:2403.17297) in
``jax.numpy``; it imports nothing of the program.

It reads parameters in the program's layout, by name:
``embed`` (V, d); ``stack/periods/b0/{norm1,norm2}/scale`` (L, d);
``mixer/{wq,wk,wv}`` (L, d, heads·dh), ``mixer/wo`` (L, heads·dh, d);
``ffn/{w_gate,w_up}`` (L, d, ff), ``ffn/w_down`` (L, ff, d);
``final_norm/scale`` (d,) and ``lm_head`` (d, V). A norm's weight is
``1 + scale``: the program stores the offset from 1.

Every product runs at ``Precision.HIGHEST``: on a TPU a float32 matmul
runs in bf16 passes unless told otherwise. ``quant=True`` is the control:
every matmul's operands are cast to float8 e4m3, each tensor scaled by its
largest magnitude, the precision below the bf16 the configurations
compute in. Layers are rematerialised and attention and the loss run in
blocks of queries, so the model fits beside nothing else at the timed
sizes."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv: int
    dh: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float


def dims_of(cfg: Dict[str, Any]) -> Dims:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(d=d, heads=h, kv=cfg["num_key_value_heads"],
                dh=cfg.get("head_dim", d // h), ff=cfg["intermediate_size"],
                vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def fp8(x):
    """x rounded to float8 e4m3 after scaling its largest magnitude to the
    format's largest, and scaled back."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def ein(spec: str, a, b, quant: bool):
    if quant:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def layer_stack(params) -> Dict[str, jnp.ndarray]:
    """The decoder layers' weights stacked over the layer axis."""
    per = params["stack"]["periods"]["b0"]
    return {"ln1": per["norm1"]["scale"], "ln2": per["norm2"]["scale"],
            "wq": per["mixer"]["wq"], "wk": per["mixer"]["wk"],
            "wv": per["mixer"]["wv"], "wo": per["mixer"]["wo"],
            "wg": per["ffn"]["w_gate"], "wu": per["ffn"]["w_up"],
            "wd": per["ffn"]["w_down"]}


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def rope(x, pos, theta):
    """Rotary embedding of (B, S, heads, dh) at positions (S,): the two
    halves of each head rotate as pairs (the rotate-half convention)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                           / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(n: int, target: int) -> int:
    b = min(n, target)
    while n % b:
        b -= 1
    return b


def attention(q, k, v, quant: bool, q_block: int = 512):
    """Causal attention. q: (B, S, heads, dh); k, v: (B, S, kv, dh); query
    head h reads key/value head h // (heads // kv)."""
    B, S, H, dh = q.shape
    kv = k.shape[2]
    g = H // kv
    qg = q.reshape(B, S, kv, g, dh)
    bq = _block(S, q_block)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * bq, bq, axis=1)
        s = ein("bqkgd,bskd->bkgqs", qb, k, quant) / math.sqrt(dh)
        qpos = i * bq + jnp.arange(bq)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("bkgqs,bskd->bqkgd", p, v, quant)

    out = jax.lax.map(one, jnp.arange(S // bq))      # (S/bq, B, bq, kv, g, dh)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * dh)


def decoder_layer(x, w, pos, dm: Dims, quant: bool):
    B, S, _ = x.shape
    h = rms_norm(x, w["ln1"], dm.eps)
    q = ein("bsd,de->bse", h, w["wq"], quant).reshape(B, S, dm.heads, dm.dh)
    k = ein("bsd,de->bse", h, w["wk"], quant).reshape(B, S, dm.kv, dm.dh)
    v = ein("bsd,de->bse", h, w["wv"], quant).reshape(B, S, dm.kv, dm.dh)
    q, k = rope(q, pos, dm.theta), rope(k, pos, dm.theta)
    x = x + ein("bse,ed->bsd", attention(q, k, v, quant), w["wo"], quant)
    h = rms_norm(x, w["ln2"], dm.eps)
    gate = ein("bsd,df->bsf", h, w["wg"], quant)
    up = ein("bsd,df->bsf", h, w["wu"], quant)
    return x + ein("bsf,fd->bsd", jax.nn.silu(gate) * up, w["wd"], quant)


def hidden(params, tokens, dm: Dims, quant: bool = False):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    x = params["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[1])

    @jax.checkpoint
    def body(x, w):
        return decoder_layer(x, w, pos, dm, quant), None

    x, _ = jax.lax.scan(body, x, layer_stack(params))
    return rms_norm(x, params["final_norm"]["scale"], dm.eps)


def logits(params, h, quant: bool = False):
    return ein("...d,dv->...v", h, params["lm_head"], quant)


def loss(params, tokens, labels, weights, dm: Dims, quant: bool = False,
         chunk: int = 512):
    """Mean cross-entropy over the tokens ``weights`` (B, S) selects."""
    h = hidden(params, tokens, dm, quant)
    B, S, d = h.shape
    c = _block(S, chunk)

    @jax.checkpoint
    def one(i):
        hc = jax.lax.dynamic_slice_in_dim(h, i * c, c, axis=1)
        yc = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        wc = jax.lax.dynamic_slice_in_dim(weights, i * c, c, axis=1)
        z = logits(params, hc, quant)
        nll = jax.nn.logsumexp(z, -1) \
            - jnp.take_along_axis(z, yc[..., None], -1)[..., 0]
        return jnp.sum(nll * wc)

    total = jnp.sum(jax.lax.map(one, jnp.arange(S // c)))
    return total / jnp.sum(weights)


# ----- AdamW, as the configurations state it ------------------------------


def cosine_lr(step: int, peak: float, warmup: int, total: int,
              floor: float = 0.1) -> float:
    """Linear warm-up to ``peak`` over ``warmup`` steps, then a cosine to
    ``floor`` · peak at step ``total``."""
    if step < warmup:
        return peak * step / max(warmup, 1)
    t = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * t))
    return floor * peak + (1 - floor) * peak * cos


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, grads, m, v, step: int, lr, opt: Dict[str, Any]):
    """One AdamW update of already clipped ``grads`` at 0-based ``step``."""
    b1, b2 = opt["b1"], opt["b2"]
    t = step + 1

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        return p - lr * (u + opt["weight_decay"] * p), m, v

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)
