"""Hierarchical 2D-mesh TP parity checks, executed in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see
tests/test_topology.py — the main pytest process keeps a single device).

Property layer: the `hypothesis` strategies sweep mesh
factorizations (1x8, 2x4, 4x2, 8x1), sequence shapes and backend choices;
every 2D-mesh run must match the flat-ring run of the same computation.
Prints one `CHECK <name> <maxerr>` line per assertion; exits non-zero on
any failure.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs import get_arch
from repro.core import tp as tp_mod
from repro.core.backends import CAISBackend, get_backend, register_backend, \
    unregister_backend
from repro.core.primitives import CAISConfig
from repro.models import build_model
from repro.runtime import Runtime, TPConfig

FAILED = []

FACTORIZATIONS = ((1, 8), (2, 4), (4, 2), (8, 1))


def check(name, err, tol=1e-6):
    print(f"CHECK {name} {err:.3e}")
    if not (err <= tol):
        FAILED.append((name, err))


def _flat_mesh():
    return sharding.make_mesh((1, 8), ("data", "model"))


def main():
    assert len(jax.devices()) == 8, jax.devices()

    d, d_ff = 32, 48
    cais = CAISConfig(num_chunks=2)
    ks = jax.random.split(jax.random.key(0), 8)
    ns = jax.random.normal(ks[0], (d,)) * 0.1 + 1.0
    wu = jax.random.normal(ks[1], (d, d_ff)) * 0.1
    wg = jax.random.normal(ks[2], (d, d_ff)) * 0.1
    wd = jax.random.normal(ks[3], (d_ff, d)) * 0.1

    cfg_at = get_arch("deepseek-7b").smoke().scaled(
        num_layers=1, d_model=d, num_heads=8, num_kv_heads=8, head_dim=8,
        d_ff=d_ff)
    cfg_gqa = cfg_at.scaled(num_kv_heads=2)
    kat = jax.random.split(jax.random.key(1), 4)
    dh = cfg_at.resolved_head_dim
    hd = cfg_at.num_heads * dh
    wq, wk, wv = (jax.random.normal(k, (d, hd)) * 0.1 for k in kat[:3])
    wo = jax.random.normal(kat[3], (hd, d)) * 0.1
    kkv = jax.random.split(jax.random.key(2), 2)
    wk2 = jax.random.normal(kkv[0], (d, 2 * dh)) * 0.1
    wv2 = jax.random.normal(kkv[1], (d, 2 * dh)) * 0.1

    # flat-ring references, one per (S, backend) — computed lazily
    refs = {}

    def flat_ref(kind, S, mode):
        key = (kind, S, mode)
        if key not in refs:
            x = jax.random.normal(jax.random.key(100 + S), (2, S, d),
                                  jnp.float32)
            tpc = tp_mod.TPContext(mesh=_flat_mesh(), backend=mode, cais=cais)
            if kind == "ffn":
                refs[key] = tp_mod.sp_ffn(tpc, x, ns, wu, wg, wd, "silu")
            elif kind == "attn":
                refs[key] = tp_mod.sp_attention(tpc, x, ns, wq, wk, wv, wo,
                                                cfg_at)
            else:  # gqa (replicated KV on the flat ring: 2 heads < 8)
                refs[key] = tp_mod.sp_attention(tpc, x, ns, wq, wk2, wv2, wo,
                                                cfg_gqa)
        return refs[key]

    # ---------------- property sweep: flat ring == 2D mesh ----------------
    # every example compiles a new program, so wall time per example says
    # nothing about the code under test: no deadline
    @settings(deadline=None)
    @given(topo=st.sampled_from(FACTORIZATIONS),
           mode=st.sampled_from(["barrier", "cais"]),
           S=st.sampled_from([8, 24, 64]),
           kind=st.sampled_from(["ffn", "attn", "gqa"]))
    def sweep(topo, mode, S, kind):
        i, o = topo
        x = jax.random.normal(jax.random.key(100 + S), (2, S, d), jnp.float32)
        mesh2d = sharding.make_tp_mesh(i, o)
        tpc = tp_mod.TPContext(mesh=mesh2d, backend=mode, cais=cais)
        if kind == "ffn":
            got = tp_mod.sp_ffn(tpc, x, ns, wu, wg, wd, "silu")
        elif kind == "attn":
            got = tp_mod.sp_attention(tpc, x, ns, wq, wk, wv, wo, cfg_at)
        else:
            got = tp_mod.sp_attention(tpc, x, ns, wq, wk2, wv2, wo, cfg_gqa)
        err = float(jnp.abs(got - flat_ref(kind, S, mode)).max())
        check(f"sweep.{kind}.{mode}.t{i}x{o}.S{S}", err)

    sweep()

    # ---------------- ragged / decode shapes: hier gemm_ar ----------------
    # S % tp != 0 (incl. S=1) can't sequence-shard; the allreduce schedule
    # must stay correct through the hierarchical composition on every
    # factorization.
    w_sq = jax.random.normal(ks[4], (d, d)) * 0.1

    @settings(deadline=None)
    @given(topo=st.sampled_from(FACTORIZATIONS),
           mode=st.sampled_from(["barrier", "cais"]),
           S=st.sampled_from([1, 3, 5]))
    def ragged(topo, mode, S):
        i, o = topo
        x = jax.random.normal(jax.random.key(200 + S), (2, S, d), jnp.float32)
        mesh2d = sharding.make_tp_mesh(i, o)
        ax = sharding.tp_axes(mesh2d)
        backend = get_backend(mode)
        y = jax.jit(sharding.shard_map(
            lambda xl, wl: backend.gemm_ar(xl, wl, ax, cais),
            mesh=mesh2d, in_specs=(P(None, None, ax), P(ax, None)),
            out_specs=P(None, None, None), check_vma=False))(x, w_sq)
        check(f"ragged.gemm_ar.{mode}.t{i}x{o}.S{S}",
              float(jnp.abs(y - x @ w_sq).max()), 1e-5)

    ragged()

    # ---------------- grouped-EP MoE: E < tp gets true EP on 2D -----------
    import dataclasses as _dc

    import repro.models.transformer as tr_mod

    cfg_moe = get_arch("mixtral-8x7b").smoke().scaled(
        num_layers=1, d_model=d, num_heads=4, num_kv_heads=4, head_dim=8,
        d_ff=d_ff, window=16)
    cfg_moe = cfg_moe.scaled(moe=_dc.replace(cfg_moe.moe,
                                             capacity_factor=8.0))
    E = cfg_moe.moe.num_experts
    assert E == 4, E                      # E=4 < tp=8: no flat EP backend
    x_moe = jax.random.normal(jax.random.key(3), (2, 64, d), jnp.float32)
    params_moe = tr_mod.init_block(jax.random.key(4), "attn", cfg_moe,
                                   jnp.float32)

    # flat tp=8 reference (E < tp replicated-expert fallback path)
    tpc_flat = tp_mod.TPContext(mesh=_flat_mesh(), backend="cais", cais=cais)
    ref_moe, ref_aux = tp_mod.sp_moe_ffn(
        tpc_flat, x_moe, params_moe["norm2"]["scale"], params_moe["ffn"],
        cfg_moe)
    for mode in ("barrier", "cais"):
        for (i, o) in ((2, 4), (4, 2)):   # E % tp_out == 0 in both
            if E % o:
                continue
            tpc2 = tp_mod.TPContext(mesh=sharding.make_tp_mesh(i, o),
                                    backend=mode, cais=cais)
            got, aux = tp_mod.sp_moe_ffn(
                tpc2, x_moe, params_moe["norm2"]["scale"],
                params_moe["ffn"], cfg_moe)
            check(f"grouped_ep.moe.{mode}.t{i}x{o}",
                  float(jnp.abs(got - ref_moe).max()), 1e-5)
            check(f"grouped_ep.moe.{mode}.t{i}x{o}.aux",
                  abs(float(aux) - float(ref_aux)), 1e-6)

    # dispatch proof: the all-to-all must only ever cross the slow tp_out
    # axis — experts are replicated across tp_in (grouped EP)
    a2a_axes = []

    class RecordingCAIS(CAISBackend):
        name = "cais-record"

        def a2a_expert_ffn(self, send, ffn, axis, cc):
            a2a_axes.append(axis)
            return super().a2a_expert_ffn(send, ffn, axis, cc)

    register_backend(RecordingCAIS())
    try:
        tpc_r = tp_mod.TPContext(mesh=sharding.make_tp_mesh(2, 4),
                                 backend="cais-record", cais=cais)
        got_r, _ = tp_mod.sp_moe_ffn(
            tpc_r, x_moe, params_moe["norm2"]["scale"], params_moe["ffn"],
            cfg_moe)
        check("grouped_ep.dispatch.parity",
              float(jnp.abs(got_r - ref_moe).max()), 1e-5)
        # the hier guard re-enters with the single slow axis: every concrete
        # (non-tuple) dispatch must name tp_out, never tp_in or the tuple
        concrete = [a for a in a2a_axes if not isinstance(a, tuple)]
        ok = (len(concrete) >= 1
              and all(a == sharding.TP_OUT_AXIS for a in concrete))
        check("grouped_ep.dispatch.tp_out_only", 0.0 if ok else 1.0)
    finally:
        unregister_backend("cais-record")

    # ---------------- full model: flat ring == 2D mesh (fwd + grads) ------
    cfg_full = get_arch("deepseek-7b").smoke().scaled(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=128)
    cfg_full_gqa = cfg_full.scaled(num_kv_heads=2)
    tokens = jax.random.randint(jax.random.key(7), (2, 32), 0,
                                cfg_full.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}

    # E=8: EP-applicable on BOTH the flat ring (E % 8 == 0) and the 2D mesh
    # (grouped EP, E % tp_out == 0) so both runs take the period-graph path
    # and the aux statistic is computed identically
    cfg_full_moe = get_arch("mixtral-8x7b").smoke().scaled(
        num_layers=2, d_model=64, num_heads=8, num_kv_heads=8, head_dim=16,
        d_ff=64, window=16)
    cfg_full_moe = cfg_full_moe.scaled(moe=_dc.replace(
        cfg_full_moe.moe, num_experts=8, capacity_factor=8.0,
        group_size=1024))
    toks_moe = jax.random.randint(jax.random.key(8), (2, 32), 0,
                                  cfg_full_moe.vocab_size)
    batch_moe = {"tokens": toks_moe, "labels": toks_moe}

    def max_leaf_err(a, b):
        errs = jax.tree.map(
            lambda u, v: float(jnp.abs(u.astype(jnp.float32)
                                       - v.astype(jnp.float32)).max()), a, b)
        return max(jax.tree.leaves(errs))

    for label, cfg_f, batch_f in (("dense", cfg_full, batch),
                                  ("gqa", cfg_full_gqa, batch),
                                  ("moe", cfg_full_moe, batch_moe)):
        for mode in ("barrier", "cais"):
            rt = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                         tp=TPConfig(mode=mode, chunks=2))
            model = build_model(cfg_f, rt)
            params = model.init(jax.random.key(0))
            outs = {}
            for name_, mesh_ in (("flat", _flat_mesh()),
                                 ("2d", sharding.make_tp_mesh(2, 4))):
                with sharding.use_mesh(mesh_):
                    outs[name_] = jax.jit(
                        jax.value_and_grad(model.loss))(params, batch_f)
            check(f"topo2d.{label}.{mode}",
                  abs(float(outs["flat"][0]) - float(outs["2d"][0])), 1e-6)
            check(f"topo2d.{label}.{mode}.grads",
                  max_leaf_err(outs["flat"][1], outs["2d"][1]), 1e-6)

    if FAILED:
        print("FAILED:", FAILED)
        sys.exit(1)
    print("ALL OK")


if __name__ == "__main__":
    main()
