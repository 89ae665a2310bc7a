"""Decoder-only LM assembled from an ArchConfig.

The layer stack runs as a ``lax.scan`` over *pattern periods* (DESIGN.md §3):
params for each entry of ``cfg.layer_pattern`` are stacked over the number of
full periods, so HLO size (and compile time) is O(period), not O(num_layers).
Remainder layers (num_layers % period) are unrolled. KV/state caches follow
the same layout and are scanned alongside params during prefill/decode.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_norm, embed_init, init_norm, softcap
from repro.runtime import Runtime

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _has_ffn(cfg: ArchConfig) -> bool:
    return cfg.d_ff > 0 or cfg.moe is not None


def init_block(key, kind: str, cfg: ArchConfig, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind in ("attn", "swa"):
        p["mixer"] = attn.init_attention(k1, cfg, dtype)
    elif kind == "mla":
        p["mixer"] = attn.init_mla(k1, cfg, dtype)
    elif kind == "ssm":
        p["mixer"] = ssm_mod.init_ssm(k1, cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_mod.init_rglru(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    if _has_ffn(cfg):
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["ffn"] = ffn_mod.init_ffn(k2, cfg, dtype)
    return p


def _mixer_forward(kind, params, x, cfg, prefix_len=0):
    if kind == "attn":
        return attn.attention_forward(params, x, cfg, window=0,
                                      prefix_len=prefix_len)
    if kind == "swa":
        return attn.attention_forward(params, x, cfg, window=cfg.window,
                                      prefix_len=prefix_len)
    if kind == "mla":
        return attn.mla_forward(params, x, cfg)
    if kind == "ssm":
        return ssm_mod.ssm_forward(params, x, cfg)
    if kind == "rglru":
        return rglru_mod.rglru_forward(params, x, cfg)
    raise ValueError(kind)


def _tp_context(rt: Runtime):
    """Build a TPContext (via the one ``TPConfig → TPContext.from_config``
    path) when an explicit collective backend is active (backends with
    ``explicit = False`` — e.g. ``auto`` — leave scheduling to XLA and run
    without shard_map)."""
    from repro.core.backends import get_backend
    from repro.core.tp import TPContext

    backend = get_backend(rt.tp.mode)
    mesh = sharding.current_mesh()
    if (not backend.explicit or mesh is None
            or sharding.tp_size(mesh) <= 1):
        return None
    return TPContext.from_config(rt.tp, mesh)


def _sp_axis(rt: Runtime, x):
    """Sequence-parallel shard axis for a (B, S, d) activation — only when
    the sequence actually divides over the model axis. Ragged/decode
    sequences (S % axis != 0, incl. S=1) stay replicated instead of hitting
    an unsatisfiable sharding constraint."""
    if not rt.tp.sequence_parallel or x.shape[1] <= 1:
        return None
    mesh = sharding.current_mesh()
    n = sharding.tp_size(mesh)
    return sharding.tp_axes(mesh) if n > 1 and x.shape[1] % n == 0 else None


def _whole_block_applicable(cfg: ArchConfig, kind: str, tp: int,
                            route_ring: Optional[int] = None) -> bool:
    """Can this block run as ONE dataflow graph (attention AND FFN/MoE side
    both explicit-TP-applicable)? Shared by the per-block and period paths
    so their gating cannot drift apart. ``route_ring`` is the MoE routing
    ring (== tp on a flat mesh; the ``tp_out`` size on a 2D mesh, where
    experts shard only over the slow axis — grouped EP)."""
    from repro.core import tp as tp_mod

    return (kind in ("attn", "swa") and tp_mod.tp_applicable(cfg, kind, tp)
            and _has_ffn(cfg)
            and (tp_mod.tp_applicable(cfg, "moe", tp, route_ring)
                 or tp_mod.tp_applicable(cfg, "ffn", tp)))


def block_forward(kind, params, x, cfg: ArchConfig, rt: Runtime,
                  prefix_len: int = 0):
    """Pre-norm residual block. Returns (x, aux_loss).

    When the whole block is TP-applicable (attention AND dense-FFN/MoE), it
    runs as ONE dataflow graph in one ``shard_map`` (``tp_mod.sp_block``):
    the graph spans the attention-out → FFN-in seam, so the optimizer's
    pass 2 fuses RS → residual → LN → AG across the sub-layer boundary and
    MoE routing goes through the IR. When the sequence can't be sharded
    over the ring (decode S=1, ragged S % tp != 0) dense blocks fall back
    *per-collective*, not per-block: the same graph without the sequence
    sharding — column/row-sharded GEMMs with one backend-dispatched
    allreduce (``gemm_ar``) per sub-layer. Blocks where only one side is
    applicable fall back to the per-sub-layer graphs below."""
    from repro.core import tp as tp_mod

    tpc = _tp_context(rt)
    dtype = x.dtype

    # ----- whole block as one dataflow graph -----
    whole = tpc is not None and _whole_block_applicable(cfg, kind, tpc.tp,
                                                        tpc.route_ring)
    if whole and x.shape[1] % tpc.tp == 0:
        x, aux = tp_mod.sp_block(tpc, x, params, cfg, kind,
                                 prefix_len=prefix_len, norm_kind=cfg.norm)
        x = sharding.shard(x, sharding.BATCH_AXES, _sp_axis(rt, x), None)
        return x, aux
    if whole and x.shape[1] % tpc.tp != 0 and cfg.moe is None:
        x, aux = tp_mod.sp_block(tpc, x, params, cfg, kind,
                                 prefix_len=prefix_len, norm_kind=cfg.norm,
                                 seq_sharded=False)
        x = sharding.shard(x, sharding.BATCH_AXES, None, None)
        return x, aux

    # ----- mixer -----
    if tpc is not None and tp_mod.tp_applicable(cfg, kind, tpc.tp) \
            and x.shape[1] % tpc.tp == 0:
        m = params["mixer"]
        x = x + tp_mod.sp_attention(
            tpc, x, params["norm1"]["scale"].astype(dtype),
            m["wq"].astype(dtype), m["wk"].astype(dtype),
            m["wv"].astype(dtype), m["wo"].astype(dtype), cfg,
            window=cfg.window if kind == "swa" else 0, prefix_len=prefix_len,
            norm_kind=cfg.norm)
    else:
        h = apply_norm(cfg.norm, params["norm1"], x)
        x = x + _mixer_forward(kind, params["mixer"], h, cfg, prefix_len)

    # ----- ffn -----
    aux = jnp.float32(0.0)
    if _has_ffn(cfg):
        if tpc is not None \
                and tp_mod.tp_applicable(cfg, "moe", tpc.tp, tpc.route_ring) \
                and x.shape[1] % tpc.tp == 0:
            out, aux = tp_mod.sp_moe_ffn(
                tpc, x, params["norm2"]["scale"].astype(dtype),
                params["ffn"], cfg, norm_kind=cfg.norm)
            x = x + out
        elif tpc is not None and tp_mod.tp_applicable(cfg, "ffn", tpc.tp) \
                and x.shape[1] % tpc.tp == 0:
            f = params["ffn"]
            x = x + tp_mod.sp_ffn(
                tpc, x, params["norm2"]["scale"].astype(dtype),
                f["w_up"].astype(dtype),
                f["w_gate"].astype(dtype) if "w_gate" in f else None,
                f["w_down"].astype(dtype), cfg.act, norm_kind=cfg.norm)
        else:
            h = apply_norm(cfg.norm, params["norm2"], x)
            out, aux = ffn_mod.ffn_forward(params["ffn"], h, cfg)
            x = x + out
    x = sharding.shard(x, sharding.BATCH_AXES, _sp_axis(rt, x), None)
    return x, aux


def _mixer_prefill(kind, params, x, cfg, s_max):
    if kind == "attn":
        return attn.attention_prefill(params, x, cfg, window=0, s_max=s_max)
    if kind == "swa":
        return attn.attention_prefill(params, x, cfg, window=cfg.window)
    if kind == "mla":
        return attn.mla_prefill(params, x, cfg, s_max=s_max)
    if kind == "ssm":
        out, (h, conv) = ssm_mod.ssm_forward(params, x, cfg, return_state=True)
        return out, {"h": h, "conv": conv}
    if kind == "rglru":
        out, (h, conv) = rglru_mod.rglru_forward(params, x, cfg,
                                                 return_state=True)
        return out, {"h": h, "conv": conv}
    raise ValueError(kind)


def block_prefill(kind, params, x, cfg, rt: Runtime, s_max):
    h = apply_norm(cfg.norm, params["norm1"], x)
    mixed, cache = _mixer_prefill(kind, params["mixer"], h, cfg, s_max)
    x = x + mixed
    if _has_ffn(cfg):
        h = apply_norm(cfg.norm, params["norm2"], x)
        out, _ = ffn_mod.ffn_forward(params["ffn"], h, cfg)
        x = x + out
    x = sharding.shard(x, sharding.BATCH_AXES, _sp_axis(rt, x), None)
    return x, cache


def _mixer_decode(kind, params, x, cache, idx, cfg):
    if kind == "attn":
        return attn.attention_decode(params, x, cache, idx, cfg, window=0)
    if kind == "swa":
        return attn.attention_decode(params, x, cache, idx, cfg,
                                     window=cfg.window)
    if kind == "mla":
        return attn.mla_decode(params, x, cache, idx, cfg)
    if kind == "ssm":
        return ssm_mod.ssm_decode(params, x, cache, cfg)
    if kind == "rglru":
        return rglru_mod.rglru_decode(params, x, cache, cfg)
    raise ValueError(kind)


def block_decode(kind, params, x, cache, idx, cfg, rt: Runtime):
    h = apply_norm(cfg.norm, params["norm1"], x)
    mixed, cache = _mixer_decode(kind, params["mixer"], h, cache, idx, cfg)
    x = x + mixed
    if _has_ffn(cfg):
        h = apply_norm(cfg.norm, params["norm2"], x)
        out, _ = ffn_mod.ffn_forward(params["ffn"], h, cfg)
        x = x + out
    x = sharding.shard(x, sharding.BATCH_AXES, None, None)
    return x, cache


def block_step(kind, params, x, pool, view, cfg, rt: Runtime):
    """Pre-norm residual block for one mixed prefill/decode serving step
    against a paged KV pool (:class:`repro.models.attention.KVView` is the
    seam). Plain-math fallback of the period-level graph path in
    :func:`_blocks_step`. Returns (x, new_pool)."""
    window = cfg.window if kind == "swa" else 0
    h = apply_norm(cfg.norm, params["norm1"], x)
    mixed, pool = attn.attention_paged(params["mixer"], h, pool, view, cfg,
                                       window=window)
    x = x + mixed
    if _has_ffn(cfg):
        h = apply_norm(cfg.norm, params["norm2"], x)
        out, _ = ffn_mod.ffn_forward(params["ffn"], h, cfg)
        x = x + out
    x = sharding.shard(x, sharding.BATCH_AXES, None, None)
    return x, pool


def _blocks_step(kinds, params_seq, x, pools_seq, view, cfg: ArchConfig,
                 rt: Runtime):
    """Run consecutive blocks of a serving step. When every block is
    whole-block TP-applicable the period executes as ONE dataflow graph in
    one ``shard_map`` (:func:`repro.core.tp.sp_serve_period`): the KV pools
    and block tables ride through the graph as extra inputs/outputs of the
    attention ``custom`` node, the out-projection/FFN reductions fuse to
    backend-dispatched ``gemm_ar`` (pass 1 — the decode/ragged TP schedule,
    S=1 and S % tp ≠ 0 alike), and ``TPConfig(planner="perfsim")`` plans the
    mixed-batch period graph. Pools are unbatched shared state, so the graph
    path additionally requires dp == 1 (no data axis to diverge replicas
    over); otherwise falls back per block."""
    from repro.core import tp as tp_mod

    tpc = _tp_context(rt)
    if (tpc is not None and len(params_seq) > 0 and cfg.moe is None
            and all(k in ("attn", "swa") for k in kinds)
            and all(_whole_block_applicable(cfg, k, tpc.tp, tpc.route_ring)
                    for k in kinds)
            and sharding.dp_size(tpc.mesh) <= 1):
        x, pools = tp_mod.sp_serve_period(tpc, x, params_seq, cfg, kinds,
                                          pools_seq, view,
                                          norm_kind=cfg.norm)
        x = sharding.shard(x, sharding.BATCH_AXES, None, None)
        return x, pools
    new_pools = []
    for kind, p, pl in zip(kinds, params_seq, pools_seq):
        x, pl = block_step(kind, p, x, pl, view, cfg, rt)
        new_pools.append(pl)
    return x, new_pools


def init_block_cache(kind, cfg: ArchConfig, batch: int, s_max: int, dtype):
    if kind == "attn":
        return attn.init_dense_cache(cfg, batch, s_max, dtype)
    if kind == "swa":
        return attn.init_swa_cache(cfg, batch, cfg.window, dtype)
    if kind == "mla":
        return attn.init_mla_cache(cfg, batch, s_max, dtype)
    if kind == "ssm":
        return ssm_mod.init_ssm_cache(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def cache_pspec(kind: str, cfg: ArchConfig):
    """PartitionSpec entries per cache leaf: batch→data axes; the long axis
    (cache sequence / state width / heads) → the TP axes (context
    parallelism; the composite ``(tp_in, tp_out)`` tuple on 2D meshes)."""
    B = sharding.BATCH_AXES
    M = sharding.tp_axes(sharding.current_mesh())
    if kind in ("attn", "swa"):
        spec = {"k": (B, M, None, None), "v": (B, M, None, None)}
        if kind == "swa":
            spec["kpos"] = (B, M)
        return spec
    if kind == "mla":
        return {"c_kv": (B, M, None), "k_rope": (B, M, None)}
    if kind == "ssm":
        return {"h": (B, M, None, None), "conv": (B, None, M)}
    if kind == "rglru":
        return {"h": (B, M), "conv": (B, None, M)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack (scan over pattern periods)
# ---------------------------------------------------------------------------


def _pattern_split(cfg: ArchConfig):
    pattern = cfg.layer_pattern
    P = len(pattern)
    n_full = cfg.num_layers // P
    rem = cfg.layer_kinds()[n_full * P:]
    return pattern, P, n_full, rem


def init_stack(key, cfg: ArchConfig, dtype):
    pattern, P, n_full, rem = _pattern_split(cfg)
    keys = jax.random.split(key, len(pattern) + len(rem))
    params: Params = {"periods": {}, "rem": []}
    for i, kind in enumerate(pattern):
        if n_full:
            params["periods"][f"b{i}"] = jax.vmap(
                lambda k, kind=kind: init_block(k, kind, cfg, dtype)
            )(jax.random.split(keys[i], n_full))
    for j, kind in enumerate(rem):
        params["rem"].append(init_block(keys[len(pattern) + j], kind, cfg, dtype))
    return params


def _blocks_forward(kinds, params_seq, x, cfg: ArchConfig, rt: Runtime,
                    prefix_len: int = 0):
    """Run consecutive blocks. When EVERY block is whole-block TP-applicable
    the run executes as ONE period-level dataflow graph in one ``shard_map``
    (``tp_mod.sp_period``) — the optimizer sees the block→block seams, so
    pass 2's cross-block RS→residual→LN→AG fusion and pass 3's asymmetric
    pairing fire inside the model path. ``rt.tp.microbatches`` (via
    ``TPContext``) additionally splits the period into independent
    microbatch chains inside that one graph, the structure pass 3 needs to
    emit ``overlap_asym`` at all on a straight-line period. Otherwise falls
    back per block."""
    from repro.core import tp as tp_mod

    tpc = _tp_context(rt)
    if (tpc is not None and len(params_seq) > 0
            and x.shape[1] % tpc.tp == 0
            and all(_whole_block_applicable(cfg, k, tpc.tp, tpc.route_ring)
                    for k in kinds)):
        x, aux = tp_mod.sp_period(tpc, x, params_seq, cfg, kinds,
                                  prefix_len=prefix_len, norm_kind=cfg.norm)
        x = sharding.shard(x, sharding.BATCH_AXES, _sp_axis(rt, x), None)
        return x, aux
    aux = jnp.float32(0.0)
    for kind, p in zip(kinds, params_seq):
        x, a = block_forward(kind, p, x, cfg, rt, prefix_len)
        aux = aux + a
    return x, aux


def stack_forward(params, x, cfg: ArchConfig, rt: Runtime,
                  prefix_len: int = 0):
    pattern, P, n_full, rem = _pattern_split(cfg)

    def period_fwd(carry, pslice):
        x, aux = carry
        x, a = _blocks_forward(pattern, [pslice[f"b{i}"] for i in range(P)],
                               x, cfg, rt, prefix_len)
        return (x, aux + a), None

    def tail_fwd(x, ps):
        return _blocks_forward(rem, ps, x, cfg, rt, prefix_len)

    # remat covers the scanned periods AND the remainder tail — a stack with
    # num_layers % len(pattern) != 0 must not silently keep tail activations
    body = jax.checkpoint(period_fwd) if rt.remat else period_fwd
    tail = jax.checkpoint(tail_fwd) if rt.remat else tail_fwd
    aux = jnp.float32(0.0)
    if n_full:
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["periods"])
    if rem:
        x, a = tail(x, params["rem"])
        aux = aux + a
    return x, aux


def stack_prefill(params, x, cfg: ArchConfig, rt: Runtime, s_max: int):
    pattern, P, n_full, rem = _pattern_split(cfg)

    def period_pf(x, pslice):
        caches = {}
        for i, kind in enumerate(pattern):
            x, caches[f"b{i}"] = block_prefill(kind, pslice[f"b{i}"], x, cfg,
                                               rt, s_max)
        return x, caches

    caches: Params = {"periods": {}, "rem": []}
    if n_full:
        x, caches["periods"] = jax.lax.scan(period_pf, x, params["periods"])
    for p, kind in zip(params["rem"], rem):
        x, c = block_prefill(kind, p, x, cfg, rt, s_max)
        caches["rem"].append(c)
    return x, caches


def stack_decode(params, x, caches, idx, cfg: ArchConfig, rt: Runtime):
    pattern, P, n_full, rem = _pattern_split(cfg)

    def period_dec(x, slices):
        pslice, cslice = slices
        new_c = {}
        for i, kind in enumerate(pattern):
            x, new_c[f"b{i}"] = block_decode(kind, pslice[f"b{i}"], x,
                                             cslice[f"b{i}"], idx, cfg, rt)
        return x, new_c

    new_caches: Params = {"periods": {}, "rem": []}
    if n_full:
        x, new_caches["periods"] = jax.lax.scan(
            period_dec, x, (params["periods"], caches["periods"]))
    for p, c, kind in zip(params["rem"], caches["rem"], rem):
        x, nc = block_decode(kind, p, x, c, idx, cfg, rt)
        new_caches["rem"].append(nc)
    return x, new_caches


def stack_step(params, x, pools, view, cfg: ArchConfig, rt: Runtime):
    """One mixed prefill/decode serving step through the whole stack: the
    paged analogue of :func:`stack_decode`, scanning period pools alongside
    period params. Supported mixers: attn/swa (gated by the engine)."""
    pattern, P, n_full, rem = _pattern_split(cfg)

    def period_step(x, slices):
        pslice, plslice = slices
        x, outs = _blocks_step(pattern, [pslice[f"b{i}"] for i in range(P)],
                               x, [plslice[f"b{i}"] for i in range(P)],
                               view, cfg, rt)
        return x, {f"b{i}": outs[i] for i in range(P)}

    new_pools: Params = {"periods": {}, "rem": []}
    if n_full:
        x, new_pools["periods"] = jax.lax.scan(
            period_step, x, (params["periods"], pools["periods"]))
    if rem:
        x, outs = _blocks_step(rem, params["rem"], x, pools["rem"], view,
                               cfg, rt)
        new_pools["rem"] = list(outs)
    return x, new_pools


def init_stack_pools(cfg: ArchConfig, num_blocks: int, block_size: int,
                     dtype):
    """Paged KV pools for the whole stack, laid out like the stack cache
    (stacked over full periods + an unrolled remainder) so the serving scan
    carries them alongside params."""
    pattern, P, n_full, rem = _pattern_split(cfg)
    pools: Params = {"periods": {}, "rem": []}
    for i, kind in enumerate(pattern):
        if n_full:
            one = attn.init_kv_pool(cfg, num_blocks, block_size, dtype)
            pools["periods"][f"b{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_full,) + a.shape), one)
    for kind in rem:
        pools["rem"].append(attn.init_kv_pool(cfg, num_blocks, block_size,
                                              dtype))
    return pools


def pool_pspec(cfg: ArchConfig):
    """PartitionSpec entries for one (num_blocks, block_size, Hkv, dh) pool:
    KV heads shard over the model axis when divisible, else replicate (the
    GQA replicated-KV layout — every device computes the full K/V
    deterministically, so replicas stay consistent)."""
    mesh = sharding.current_mesh()
    tp = sharding.tp_size(mesh)
    head = sharding.tp_axes(mesh) if tp > 1 and cfg.num_kv_heads % tp == 0 \
        else None
    return (None, None, head, None)


def shard_stack_pools(pools, cfg: ArchConfig):
    """Apply sharding constraints to a stack-pools pytree."""
    spec = pool_pspec(cfg)

    def do(tree, stacked):
        return {name: sharding.shard(leaf, *((None,) if stacked else ())
                                     + spec)
                for name, leaf in tree.items()}

    out: Params = {"periods": {}, "rem": []}
    for name, tree in pools["periods"].items():
        out["periods"][name] = do(tree, True)
    for tree in pools["rem"]:
        out["rem"].append(do(tree, False))
    return out


def init_stack_cache(cfg: ArchConfig, batch: int, s_max: int, dtype):
    pattern, P, n_full, rem = _pattern_split(cfg)
    caches: Params = {"periods": {}, "rem": []}
    for i, kind in enumerate(pattern):
        if n_full:
            one = init_block_cache(kind, cfg, batch, s_max, dtype)
            caches["periods"][f"b{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_full,) + a.shape), one)
    for kind in rem:
        caches["rem"].append(init_block_cache(kind, cfg, batch, s_max, dtype))
    return caches


def shard_stack_cache(caches, cfg: ArchConfig):
    """Apply sharding constraints to a stack cache pytree."""
    pattern, P, n_full, rem = _pattern_split(cfg)

    def do(tree, kind, stacked):
        spec = cache_pspec(kind, cfg)
        return {
            name: sharding.shard(leaf, *((None,) if stacked else ())
                                 + tuple(spec[name]))
            for name, leaf in tree.items()
        }

    out: Params = {"periods": {}, "rem": []}
    for i, kind in enumerate(pattern):
        if n_full:
            out["periods"][f"b{i}"] = do(caches["periods"][f"b{i}"], kind, True)
    for c, kind in zip(caches["rem"], rem):
        out["rem"].append(do(c, kind, False))
    return out


# ---------------------------------------------------------------------------
# LM head / loss
# ---------------------------------------------------------------------------


def chunked_ce_loss(x, embed_or_head, labels, mask, cfg: ArchConfig,
                    rt: Runtime, tied: bool):
    """Cross-entropy with logits computed per sequence chunk (bounds the
    (B, Sc, V) tensor for 256k-vocab archs). x: (B,S,d)."""
    B, S, d = x.shape
    chunk = min(rt.loss_chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk

    w = embed_or_head  # (V, d) if tied else (d, V)

    def chunk_loss(xc, yc, mc):
        dtype = xc.dtype
        logits = xc @ (w.T if tied else w).astype(dtype)
        logits = softcap(logits.astype(jnp.float32), cfg.logits_softcap)
        logits = sharding.shard(logits, sharding.BATCH_AXES, None,
                                sharding.tp_axes(sharding.current_mesh()))
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]
        nll = (lse - gold) * mc
        return jnp.sum(nll), jnp.sum(mc)

    if n == 1:
        tot, cnt = chunk_loss(x, labels, mask.astype(jnp.float32))
    else:
        xs = (x.reshape(B, n, chunk, d).transpose(1, 0, 2, 3),
              labels.reshape(B, n, chunk).transpose(1, 0, 2),
              mask.astype(jnp.float32).reshape(B, n, chunk).transpose(1, 0, 2))

        def body(carry, inp):
            tot, cnt = carry
            t, c = chunk_loss(*inp)
            return (tot + t, cnt + c), None

        (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), xs)
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# LM — the top-level decoder-only model
# ---------------------------------------------------------------------------

AUX_LOSS_WEIGHT = 0.01


class LM:
    """Decoder-only language model (all non-enc-dec archs)."""

    def __init__(self, cfg: ArchConfig, rt: Runtime = Runtime()):
        self.cfg = cfg
        self.rt = rt

    # ----- params -----
    def init(self, key) -> Params:
        cfg, dtype = self.cfg, self.rt.pdtype
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p: Params = {
            "embed": embed_init(k1, (cfg.vocab_size, cfg.d_model), dtype),
            "stack": init_stack(k2, cfg, dtype),
            "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(k3, (cfg.d_model, cfg.vocab_size), dtype)
        return p

    def _head(self, params):
        tied = self.cfg.tie_embeddings
        return (params["embed"] if tied else params["lm_head"]), tied

    def _embed(self, params, tokens, dtype):
        with jax.named_scope("model.embed"):
            e = params["embed"].astype(dtype)[tokens]
            return sharding.shard(e, sharding.BATCH_AXES, None, None)

    # ----- training -----
    def forward(self, params, tokens):
        """Full hidden states (B,S,d) — logits computed by the loss/head."""
        dtype = self.rt.dtype
        x = self._embed(params, tokens, dtype)
        x, aux = stack_forward(params["stack"], x, self.cfg, self.rt)
        with jax.named_scope("model.head"):
            x = apply_norm(self.cfg.norm, params["final_norm"], x)
        return x, aux

    def loss(self, params, batch) -> jnp.ndarray:
        tokens, labels = batch["tokens"], batch["labels"]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        x, aux = self.forward(params, tokens)
        head, tied = self._head(params)
        with jax.named_scope("model.head"):
            ce = chunked_ce_loss(x, head, labels, mask, self.cfg, self.rt,
                                 tied)
        return ce + AUX_LOSS_WEIGHT * aux

    # ----- serving -----
    def logits(self, params, x):
        head, tied = self._head(params)
        logits = x @ (head.T if tied else head).astype(x.dtype)
        logits = softcap(logits.astype(jnp.float32), self.cfg.logits_softcap)
        return sharding.shard(logits, sharding.BATCH_AXES, None,
                              sharding.tp_axes(sharding.current_mesh()))

    def prefill(self, params, tokens, s_max: Optional[int] = None):
        """Returns (last-position logits, caches). ``tokens`` may be the raw
        (B,S) array or a batch dict with a "tokens" entry (uniform API)."""
        if isinstance(tokens, dict):
            tokens = tokens["tokens"]
        dtype = self.rt.dtype
        s_max = s_max or tokens.shape[1]
        x = self._embed(params, tokens, dtype)
        x, caches = stack_prefill(params["stack"], x, self.cfg, self.rt, s_max)
        x = apply_norm(self.cfg.norm, params["final_norm"], x[:, -1:])
        caches = shard_stack_cache(caches, self.cfg)
        return self.logits(params, x), caches

    def decode_step(self, params, token, caches, idx):
        """token: (B,1) int32; idx: (B,) positions. Returns (logits, caches)."""
        dtype = self.rt.dtype
        x = self._embed(params, token, dtype)
        x, caches = stack_decode(params["stack"], x, caches, idx, self.cfg,
                                 self.rt)
        x = apply_norm(self.cfg.norm, params["final_norm"], x)
        caches = shard_stack_cache(caches, self.cfg)
        return self.logits(params, x), caches

    def init_cache(self, batch: int, s_max: int):
        return init_stack_cache(self.cfg, batch, s_max, self.rt.dtype)

    # ----- paged serving (docs/serving.md) -----
    def init_pools(self, num_blocks: int, block_size: int):
        return init_stack_pools(self.cfg, num_blocks, block_size,
                                self.rt.dtype)

    def serve_step(self, params, tokens, pools, view):
        """One mixed prefill/decode step against paged KV pools.
        tokens: (B, S_step) int32 (0 at padding positions); ``view`` is the
        :class:`repro.models.attention.KVView` seam. Returns (per-row logits
        at each row's last valid position, (B, 1, V), and the new pools)."""
        dtype = self.rt.dtype
        x = self._embed(params, tokens, dtype)
        x, pools = stack_step(params["stack"], x, pools, view, self.cfg,
                              self.rt)
        B = x.shape[0]
        x_last = x[jnp.arange(B), view.last][:, None, :]
        x_last = apply_norm(self.cfg.norm, params["final_norm"], x_last)
        pools = shard_stack_pools(pools, self.cfg)
        return self.logits(params, x_last), pools
