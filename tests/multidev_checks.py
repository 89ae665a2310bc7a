"""Multi-device correctness checks, executed in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (so the main pytest
process keeps a single device; see tests/test_multidevice.py). Arguments
name the parts to run (default: all of ``PARTS``).

Prints one `CHECK <name> <maxerr>` line per assertion; exits non-zero on any
failure.
"""
import dataclasses as _dc
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.models.transformer as tr_mod
from repro import sharding
from repro.configs import get_arch
from repro.core import dataflow as df
from repro.core import primitives as prim
from repro.core import tp as tp_mod
from repro.core.primitives import CAISConfig
from repro.core.primitives import CAISConfig as CC
from repro.models import build_model
from repro.models.layers import activation, apply_norm
from repro.runtime import Runtime, TPConfig

FAILED = []
# The suite runs for about half an hour on the CPU, so it is split into
# parts that each fit one subprocess: ``python multidev_checks.py <part>``.
PARTS = ("rings", "period", "split", "model")


def check(name, err, tol=1e-4):
    print(f"CHECK {name} {err:.3e}")
    if not (err <= tol):
        FAILED.append((name, err))


def main(parts=PARTS):
    unknown = set(parts) - set(PARTS)
    assert not unknown, f"unknown parts {sorted(unknown)}; have {PARTS}"
    assert len(jax.devices()) == 8, jax.devices()

    # ---------------- inputs several parts read (cheap to build) ---------
    B, S, d, F = 2, 64, 32, 48
    x = jax.random.normal(jax.random.key(0), (B, S, d), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (d, F), jnp.float32) * 0.1
    ref = x @ w
    mesh = sharding.make_mesh((8,), ("model",))
    mesh4 = sharding.make_mesh((2, 4), ("data", "model"))
    d_ff = 96
    ksub = jax.random.split(jax.random.key(20), 4)
    ns = jax.random.normal(ksub[0], (d,)) * 0.1 + 1.0
    wu4 = jax.random.normal(ksub[1], (d, d_ff)) * 0.1
    wg4 = jax.random.normal(ksub[2], (d, d_ff)) * 0.1
    wd4 = jax.random.normal(ksub[3], (d_ff, d)) * 0.1
    cais4 = CC(num_chunks=2)
    cfg_at = get_arch("deepseek-7b").smoke().scaled(
        num_layers=1, d_model=d, num_heads=4, num_kv_heads=4, head_dim=8,
        d_ff=d_ff)
    cfg_blk = cfg_at                                  # dense, kv sharded
    cfg_blk_gqa = cfg_at.scaled(num_kv_heads=2)       # replicated KV
    cfg_blk_moe = get_arch("mixtral-8x7b").smoke().scaled(
        num_layers=1, d_model=d, num_heads=4, num_kv_heads=4, head_dim=8,
        d_ff=d_ff, window=16)
    cfg_blk_moe = cfg_blk_moe.scaled(moe=_dc.replace(
        cfg_blk_moe.moe, capacity_factor=8.0))
    assert cfg_blk_moe.moe.num_experts % 4 == 0
    x4 = jax.random.normal(jax.random.key(40), (4, 64, d), jnp.float32)

    if "rings" in parts:
        # ------------ primitives on TP rings of size 2 / 4 / 8 ------------
        for ring in (2, 4, 8):
            rmesh = sharding.make_mesh((8 // ring, ring), ("data", "model"))
            cais = CAISConfig(num_chunks=2, bidirectional=True)
            y = jax.jit(sharding.shard_map(
                lambda xl, wl: prim.ag_gemm(xl, wl, "model", cais),
                mesh=rmesh, in_specs=(P(None, "model", None), P(None, "model")),
                out_specs=P(None, None, "model"), check_vma=False))(x, w)
            check(f"ag_gemm.ring{ring}", float(jnp.abs(y - ref).max()))
            y2 = jax.jit(sharding.shard_map(
                lambda xl, wl: prim.gemm_rs(xl, wl, "model", cais),
                mesh=rmesh, in_specs=(P(None, None, "model"), P("model", None)),
                out_specs=P(None, "model", None), check_vma=False))(x, w)
            check(f"gemm_rs.ring{ring}", float(jnp.abs(y2 - ref).max()))

        for chunks in (1, 2, 4):
            for bidir in (False, True):
                cais = CAISConfig(num_chunks=chunks, bidirectional=bidir)
                y = jax.jit(sharding.shard_map(
                    lambda xl, wl: prim.ag_gemm(xl, wl, "model", cais),
                    mesh=mesh, in_specs=(P(None, "model", None), P(None, "model")),
                    out_specs=P(None, None, "model"), check_vma=False))(x, w)
                check(f"ag_gemm.c{chunks}.b{int(bidir)}",
                      float(jnp.abs(y - ref).max()))
                y2 = jax.jit(sharding.shard_map(
                    lambda xl, wl: prim.gemm_rs(xl, wl, "model", cais),
                    mesh=mesh, in_specs=(P(None, None, "model"), P("model", None)),
                    out_specs=P(None, "model", None), check_vma=False))(x, w)
                check(f"gemm_rs.c{chunks}.b{int(bidir)}",
                      float(jnp.abs(y2 - ref).max()))

        cais = CAISConfig(num_chunks=2)
        y3 = jax.jit(sharding.shard_map(
            lambda xl, wl: prim.gemm_ar(xl, wl, "model", cais),
            mesh=mesh, in_specs=(P(None, None, "model"), P("model", None)),
            out_specs=P(None, None, None), check_vma=False))(x, w)
        check("gemm_ar", float(jnp.abs(y3 - ref).max()))

        x2 = jax.random.normal(jax.random.key(2), (B, S, d))
        w2 = jax.random.normal(jax.random.key(3), (d, F)) * 0.1
        o1, o2 = jax.jit(sharding.shard_map(
            lambda a, b, c, e: prim.overlap_asymmetric((a, b), (c, e), "model",
                                                       cais),
            mesh=mesh,
            in_specs=(P(None, None, "model"), P("model", None),
                      P(None, "model", None), P(None, "model")),
            out_specs=(P(None, "model", None), P(None, None, "model")),
            check_vma=False))(x, w, x2, w2)
        check("overlap_asym.rs", float(jnp.abs(o1 - ref).max()))
        check("overlap_asym.ag", float(jnp.abs(o2 - x2 @ w2).max()))

        # ---------------- dataflow optimizer ----------------
        g = df.sublayer_graph()
        opt = df.optimize(g)
        assert [n.op for n in opt.nodes if n.op != "input"] == ["fused_rs_ln_ag"]
        w1 = jax.random.normal(jax.random.key(4), (d, F)) * 0.1
        scale = jax.random.normal(jax.random.key(5), (F,)) * 0.1
        wu = jax.random.normal(jax.random.key(6), (F, d)) * 0.1
        refdf = df.execute(g, {"x": x}, {"w1": w1, "scale": scale, "w2": wu})[0]

        def run_graph(graph, backend="cais"):
            def local(x, w1, scale, w2):
                return df.execute(graph, {"x": x},
                                  {"w1": w1, "scale": scale, "w2": w2},
                                  axis="model", cais=cais, backend=backend)
            return jax.jit(sharding.shard_map(
                local, mesh=mesh,
                in_specs=(P(None, None, "model"), P("model", None), P(),
                          P(None, "model")),
                out_specs=(P(None, None, "model"),), check_vma=False))(
                    x, w1, scale, wu)[0]

        check("dataflow.unopt", float(jnp.abs(run_graph(g) - refdf).max()), 1e-3)
        check("dataflow.opt", float(jnp.abs(run_graph(opt) - refdf).max()), 1e-3)
        check("dataflow.opt_barrier",
              float(jnp.abs(run_graph(opt, "barrier") - refdf).max()), 1e-3)

        # ---------------- graph-routed sub-layers vs hand-fused ---------------
        # sp_ffn / sp_attention now build + optimize + execute a dataflow graph;
        # pin them to the pre-refactor hand-fused schedules (written out inline
        # with the raw primitives) on a 4-way ring, per backend.

        def hand_fused_ffn(mode):
            """The pre-refactor sp_ffn local body (tp.py@636bb1c)."""
            def local(x, ns, wu, wg, wd):
                xn = apply_norm("rmsnorm", {"scale": ns}, x)
                if mode == "barrier":
                    h = prim.barrier_ag_gemm(xn, wu, "model")
                    g_ = prim.barrier_ag_gemm(xn, wg, "model")
                    h = activation("silu", g_) * h
                    return prim.barrier_gemm_rs(h, wd, "model")
                outs = prim.ag_gemm_multi(xn, (wu, wg), "model", cais4)
                h = activation("silu", outs[1]) * outs[0]
                return prim.gemm_rs(h, wd, "model", cais4)
            return jax.jit(sharding.shard_map(
                local, mesh=mesh4,
                in_specs=(P(None, "model", None), P(None,), P(None, "model"),
                          P(None, "model"), P("model", None)),
                out_specs=P(None, "model", None), check_vma=False))(
                    x, ns, wu4, wg4, wd4)

        for mode in ("barrier", "cais"):
            tpc4 = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=cais4)
            got = tp_mod.sp_ffn(tpc4, x, ns, wu4, wg4, wd4, "silu")
            check(f"sp_ffn.graph_vs_handfused.{mode}",
                  float(jnp.abs(got - hand_fused_ffn(mode)).max()), 1e-5)
            # auto-planned chunking must agree with static chunking numerics
            tpc4p = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=CC())
            gotp = tp_mod.sp_ffn(tpc4p, x, ns, wu4, wg4, wd4, "silu")
            check(f"sp_ffn.planned_chunks.{mode}",
                  float(jnp.abs(gotp - hand_fused_ffn(mode)).max()), 1e-5)

        from repro.models.attention import attention_core
        from repro.models.layers import apply_rope

        kat = jax.random.split(jax.random.key(21), 4)
        wq4, wk4, wv4, wo4 = (jax.random.normal(k, (d, d)) * 0.1 for k in kat)
        H, dh = cfg_at.num_heads, cfg_at.resolved_head_dim

        def hand_fused_attn(mode):
            """The pre-refactor sp_attention local body (tp.py@636bb1c),
            dense-heads case (kv sharded)."""
            def local(x, ns, wq, wk, wv, wo):
                xn = apply_norm("rmsnorm", {"scale": ns}, x)
                if mode == "barrier":
                    q = prim.barrier_ag_gemm(xn, wq, "model")
                    k = prim.barrier_ag_gemm(xn, wk, "model")
                    v = prim.barrier_ag_gemm(xn, wv, "model")
                else:
                    q, k, v = prim.ag_gemm_multi(xn, (wq, wk, wv), "model", cais4)
                B_, S = q.shape[0], q.shape[1]
                H_loc = H // 4
                pos = jnp.broadcast_to(jnp.arange(S), (B_, S))
                q = apply_rope(q.reshape(B_, S, H_loc, dh), pos,
                               cfg_at.rope_theta)
                k = apply_rope(k.reshape(B_, S, H_loc, dh), pos,
                               cfg_at.rope_theta)
                v = v.reshape(B_, S, H_loc, dh)
                o = attention_core(q, k, v, q_positions=pos, kv_positions=pos,
                                   causal=True)
                o = o.reshape(B_, S, H_loc * dh)
                if mode == "barrier":
                    return prim.barrier_gemm_rs(o, wo, "model")
                return prim.gemm_rs(o, wo, "model", cais4)
            return jax.jit(sharding.shard_map(
                local, mesh=mesh4,
                in_specs=(P(None, "model", None), P(None,), P(None, "model"),
                          P(None, "model"), P(None, "model"), P("model", None)),
                out_specs=P(None, "model", None), check_vma=False))(
                    x, ns, wq4, wk4, wv4, wo4)

        for mode in ("barrier", "cais"):
            tpc4 = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=cais4)
            got = tp_mod.sp_attention(tpc4, x, ns, wq4, wk4, wv4, wo4, cfg_at)
            check(f"sp_attention.graph_vs_handfused.{mode}",
                  float(jnp.abs(got - hand_fused_attn(mode)).max()), 1e-5)

        # replicated-KV (GQA, Hkv < tp): K/V weights replicate and the custom
        # core slices per-device heads via axis_index — pin against a mesh-free
        # dense reference (attention_core handles grouped heads natively)
        cfg_gqa = cfg_at.scaled(num_kv_heads=2)
        kkv = jax.random.split(jax.random.key(22), 2)
        wk2 = jax.random.normal(kkv[0], (d, 2 * dh)) * 0.1
        wv2 = jax.random.normal(kkv[1], (d, 2 * dh)) * 0.1
        xn_full = apply_norm("rmsnorm", {"scale": ns}, x)
        pos_full = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                    (x.shape[0], x.shape[1]))
        q_ref = apply_rope((xn_full @ wq4).reshape(x.shape[0], x.shape[1], H, dh),
                           pos_full, cfg_gqa.rope_theta)
        k_ref = apply_rope((xn_full @ wk2).reshape(x.shape[0], x.shape[1], 2, dh),
                           pos_full, cfg_gqa.rope_theta)
        v_ref = (xn_full @ wv2).reshape(x.shape[0], x.shape[1], 2, dh)
        o_ref = attention_core(q_ref, k_ref, v_ref, q_positions=pos_full,
                               kv_positions=pos_full, causal=True)
        gqa_ref = o_ref.reshape(x.shape[0], x.shape[1], H * dh) @ wo4
        for mode in ("barrier", "cais"):
            tpc4 = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=cais4)
            got = tp_mod.sp_attention(tpc4, x, ns, wq4, wk2, wv2, wo4, cfg_gqa)
            check(f"sp_attention.gqa_replicated_kv.{mode}",
                  float(jnp.abs(got - gqa_ref).max()), 1e-5)

        # ---------------- whole-block graph vs PR-1 per-sub-layer path --------
        # sp_block builds ONE dataflow graph per transformer block (pass 2 fuses
        # the attention-out → FFN-in seam into fused_rs_ln_ag_multi); pin it to
        # the split sp_attention + sp_ffn / sp_moe_ffn composition on the 4-way
        # ring for dense, GQA, and MoE blocks, per backend, at 1e-6.

        def split_block(tpc, x, params, cfg):
            p, mm = params, params["mixer"]
            r1 = x + tp_mod.sp_attention(
                tpc, x, p["norm1"]["scale"], mm["wq"], mm["wk"], mm["wv"],
                mm["wo"], cfg)
            if cfg.moe is not None:
                out, aux_ = tp_mod.sp_moe_ffn(tpc, r1, p["norm2"]["scale"],
                                              p["ffn"], cfg)
                return r1 + out, aux_
            f_ = p["ffn"]
            return r1 + tp_mod.sp_ffn(tpc, r1, p["norm2"]["scale"], f_["w_up"],
                                      f_.get("w_gate"), f_["w_down"],
                                      cfg.act), jnp.float32(0.0)

        for label, cfg_b in (("dense", cfg_blk), ("gqa", cfg_blk_gqa),
                             ("moe", cfg_blk_moe)):
            params_b = tr_mod.init_block(jax.random.key(23), "attn", cfg_b,
                                         jnp.float32)
            for mode in ("barrier", "cais"):
                tpc4 = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=cais4)
                got, aux_g = tp_mod.sp_block(tpc4, x, params_b, cfg_b, "attn")
                ref, aux_r = split_block(tpc4, x, params_b, cfg_b)
                check(f"block_graph.{label}.{mode}",
                      float(jnp.abs(got - ref).max()), 1e-6)
                check(f"block_graph.{label}.{mode}.aux",
                      abs(float(aux_g) - float(aux_r)), 1e-6)
            # the block graph must actually carry the cross-sub-layer fusion
            if cfg_b.moe is None:
                core = tp_mod._attention_core_fn(cfg_b, 4)
                opt = df.optimize(tp_mod.dense_block_graph(
                    core, True, cfg_b.act))
                ops = [n.op for n in opt.nodes]
                check(f"block_graph.{label}.pass2_fired",
                      0.0 if "fused_rs_ln_ag_multi" in ops else 1.0)

        # E < tp owner mapping (replicated expert weights, zero-capacity
        # padding): the shared routing closures must agree with a 1-device run
        # of the same params (capacity large enough that no token drops)
        params_ep = tr_mod.init_block(jax.random.key(24), "attn", cfg_blk_moe,
                                      jnp.float32)
        mesh8x = sharding.make_mesh((1, 8), ("data", "model"))   # tp=8 > E=4
        mesh1x = sharding.make_mesh((1, 1), ("data", "model"))
        outs_ep = {}
        for name_, mesh_ in (("tp8", mesh8x), ("tp1", mesh1x)):
            tpc_ = tp_mod.TPContext(mesh=mesh_, backend="cais", cais=cais4)
            outs_ep[name_], _ = tp_mod.sp_moe_ffn(
                tpc_, x, params_ep["norm2"]["scale"], params_ep["ffn"],
                cfg_blk_moe)
        check("sp_moe_ffn.e_lt_tp",
              float(np.abs(np.asarray(outs_ep["tp8"])
                           - np.asarray(outs_ep["tp1"])).max()), 1e-5)

    if "period" in parts:
        # ---------------- period-level graph vs per-block composition ---------
        # sp_period concatenates ≥2 block fragments into ONE graph / ONE
        # shard_map (pass 2 fuses the block→block rs→residual→ln→ag seam); pin
        # it to the per-block sp_block composition at 1e-6 on the 4-way ring for
        # dense, GQA, MoE, and a mixed attn/swa pattern, per backend.
        cfg_mixed = cfg_blk.scaled(window=16, layer_pattern=("attn", "swa"))
        for label, cfg_p, kinds_p in (
                ("dense", cfg_blk, ("attn", "attn")),
                ("gqa", cfg_blk_gqa, ("attn", "attn")),
                ("moe", cfg_blk_moe, ("attn", "attn")),
                ("mixed", cfg_mixed, ("attn", "swa"))):
            ps = [tr_mod.init_block(jax.random.key(30 + j), k_, cfg_p,
                                    jnp.float32)
                  for j, k_ in enumerate(kinds_p)]
            for mode in ("barrier", "cais"):
                tpc4 = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=cais4)
                got, aux_g = tp_mod.sp_period(tpc4, x, ps, cfg_p, kinds_p)
                refx, refaux = x, jnp.float32(0.0)
                for p_, k_ in zip(ps, kinds_p):
                    refx, a_ = tp_mod.sp_block(tpc4, refx, p_, cfg_p, k_)
                    refaux = refaux + a_
                check(f"period_graph.{label}.{mode}",
                      float(jnp.abs(got - refx).max()), 1e-6)
                check(f"period_graph.{label}.{mode}.aux",
                      abs(float(aux_g) - float(refaux)), 1e-6)

    if "split" in parts:
        # ---------------- microbatch-split period vs unsplit ------------------
        # sp_period(num_microbatches=2) splits the batch into two independent
        # chains merged into ONE graph (shared weights) re-concatenated inside
        # the same shard_map — the structure pass 3 turns into overlap_asym.
        # Acceptance (ISSUE 5): ≤1e-6 output parity vs the unsplit period on
        # the 4-way ring for dense/GQA/MoE, per backend. Dense/GQA aux is
        # trivially zero and checked; MoE aux is a load-balance statistic that
        # is not linear over sub-batches (mean of per-chain means ≠ full-batch
        # mean), so only the outputs are pinned there.
        for label, cfg_p in (("dense", cfg_blk), ("gqa", cfg_blk_gqa),
                             ("moe", cfg_blk_moe)):
            kinds_p = ("attn", "attn")
            ps_mb = [tr_mod.init_block(jax.random.key(50 + j), k_, cfg_p,
                                       jnp.float32)
                     for j, k_ in enumerate(kinds_p)]
            for mode in ("barrier", "cais"):
                tpc4 = tp_mod.TPContext(mesh=mesh4, backend=mode, cais=cais4)
                got1, aux1 = tp_mod.sp_period(tpc4, x4, ps_mb, cfg_p, kinds_p,
                                              num_microbatches=1)
                got2, aux2 = tp_mod.sp_period(tpc4, x4, ps_mb, cfg_p, kinds_p,
                                              num_microbatches=2)
                check(f"period_split.{label}.{mode}",
                      float(jnp.abs(got2 - got1).max()), 1e-6)
                if cfg_p.moe is None:
                    check(f"period_split.{label}.{mode}.aux",
                          abs(float(aux2) - float(aux1)), 1e-6)
            # the "auto" heuristic resolves (to 1 at these smoke payloads) and
            # stays correct end to end
            tpc4a = tp_mod.TPContext(mesh=mesh4, backend="cais", cais=cais4,
                                     num_microbatches="auto")
            gota, _ = tp_mod.sp_period(tpc4a, x4, ps_mb, cfg_p, kinds_p)
            check(f"period_split.{label}.auto",
                  float(jnp.abs(gota - got1).max()), 1e-6)
        # the model path reaches the split via the Runtime knob
        rt_mb = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                        tp=TPConfig(mode="cais", chunks=2, microbatches=2))
        rt_u = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                       tp=TPConfig(mode="cais", chunks=2))
        ps_rt = [tr_mod.init_block(jax.random.key(55 + j), "attn", cfg_blk,
                                   jnp.float32) for j in range(2)]
        outs_rt = {}
        for name_, rt_ in (("split", rt_mb), ("unsplit", rt_u)):
            with sharding.use_mesh(mesh4):
                outs_rt[name_], _ = tr_mod._blocks_forward(
                    ("attn", "attn"), ps_rt, x4, cfg_blk, rt_)
        check("period_split.runtime_knob",
              float(jnp.abs(outs_rt["split"] - outs_rt["unsplit"]).max()), 1e-6)

    if "model" in parts:
        # ---------------- perfsim planner vs greedy (repro.plan) --------------
        # tp_planner="perfsim" routes pass 3 + the microbatch choice through the
        # simulated-makespan search (plan cache pointed at a tempdir here); the
        # schedule may differ but the math may not: ≤1e-6 parity vs the greedy
        # planner on the 4-way ring, 2-block split period, per backend
        # (ISSUE 6 acceptance).
        import tempfile as _tf

        import repro.plan as plan_mod
        from repro.plan import cache as plan_cache
        _saved_cache = plan_cache._DEFAULT
        plan_cache._DEFAULT = plan_mod.PlanCache(root=_tf.mkdtemp())
        try:
            ps_pl = [tr_mod.init_block(jax.random.key(60 + j), "attn", cfg_blk,
                                       jnp.float32) for j in range(2)]
            for mode in ("barrier", "cais"):
                outs_pl = {}
                for planner in ("greedy", "perfsim"):
                    tpc4p = tp_mod.TPContext(mesh=mesh4, backend=mode,
                                             cais=cais4, planner=planner)
                    outs_pl[planner], _ = tp_mod.sp_period(
                        tpc4p, x4, ps_pl, cfg_blk, ("attn", "attn"),
                        num_microbatches=2)
                check(f"planner.perfsim_vs_greedy.{mode}",
                      float(jnp.abs(outs_pl["perfsim"]
                                    - outs_pl["greedy"]).max()), 1e-6)
            st_pl = plan_cache._DEFAULT.stats
            check("planner.cache_observable",
                  0.0 if st_pl["misses"] >= 1 else 1.0)
        finally:
            plan_cache._DEFAULT = _saved_cache

        # ---------------- decode-path TP (S=1: no sequence sharding) ----------
        # S=1 can't shard the sequence over the ring, but row/col-sharded GEMMs
        # don't need it: block_forward must route dense blocks through the
        # allreduce schedule (backend gemm_ar) instead of silently unsharding.
        from repro.core.backends import (CAISBackend, register_backend,
                                         unregister_backend)

        ar_calls = {"n": 0}

        class CountingCAIS(CAISBackend):
            name = "cais-count"

            def gemm_ar(self, xl, wl, axis, cc):
                ar_calls["n"] += 1
                return super().gemm_ar(xl, wl, axis, cc)

        register_backend(CountingCAIS())
        try:
            params_dec = tr_mod.init_block(jax.random.key(25), "attn", cfg_blk,
                                           jnp.float32)
            x1 = x[:, :1]                                   # (B, 1, d)
            outs_dec = {}
            for mode in ("cais-count", "auto"):
                rt_dec = Runtime(compute_dtype="float32", remat=False,
                                 loss_chunk=16, tp=TPConfig(mode=mode, chunks=2))
                with sharding.use_mesh(mesh4):
                    outs_dec[mode], _ = tr_mod.block_forward(
                        "attn", params_dec, x1, cfg_blk, rt_dec)
            check("decode.s1_block_parity",
                  float(jnp.abs(outs_dec["cais-count"]
                                - outs_dec["auto"]).max()), 1e-4)
            # two sub-layers → two backend-dispatched allreduces traced
            check("decode.s1_backend_dispatch",
                  0.0 if ar_calls["n"] >= 2 else 1.0)
        finally:
            unregister_backend("cais-count")

        # ragged S (S % tp != 0, S > 1): dense blocks keep TP via the allreduce
        # schedule, the MoE fallback must not die on an unsatisfiable
        # sequence-parallel / group sharding constraint
        x3 = x[:, :3]
        outs_rag = {}
        for mode in ("cais", "auto"):
            rt_rag = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                             tp=TPConfig(mode=mode, chunks=2))
            with sharding.use_mesh(mesh4):
                outs_rag[mode], _ = tr_mod.block_forward(
                    "attn", params_dec, x3, cfg_blk, rt_rag)
        check("decode.ragged_s_parity",
              float(jnp.abs(outs_rag["cais"] - outs_rag["auto"]).max()), 1e-4)
        rt_rag = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                         tp=TPConfig(mode="cais", chunks=2))
        params_rag_moe = tr_mod.init_block(jax.random.key(26), "attn",
                                           cfg_blk_moe, jnp.float32)
        with sharding.use_mesh(mesh4):
            out_rm, _ = tr_mod.block_forward("attn", params_rag_moe, x3,
                                             cfg_blk_moe, rt_rag)
        check("decode.ragged_s_moe_runs",
              0.0 if out_rm.shape == x3.shape else 1.0)

        # ---------------- serving: paged KV through the serve-period graph ----
        # S=1 decode rows and chunked-prefill rows with S % tp != 0 must BOTH
        # keep TP via backend-dispatched gemm_ar (never silently unshard), and a
        # mixed prefill+decode batch must match the same rows run in
        # single-mode batches.
        from repro.models.attention import KVView

        mesh14 = sharding.make_mesh((1, 4), ("data", "model"))
        cfg_srv = get_arch("deepseek-7b").smoke().scaled(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
            d_ff=128)
        params_srv = None

        def serve_views():
            bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
            pad = -jnp.ones((1, 5), jnp.int32)
            v_pre_a = KVView(bt, jnp.concatenate(
                [jnp.arange(5, dtype=jnp.int32)[None, :], pad]),
                jnp.asarray([5, 0], jnp.int32), jnp.asarray([4, 0], jnp.int32))
            v_dec = KVView(bt, jnp.asarray([[5], [-1]], jnp.int32),
                           jnp.asarray([6, 0], jnp.int32),
                           jnp.asarray([0, 0], jnp.int32))
            v_pre_b = KVView(bt, jnp.asarray([[-1] * 3, [0, 1, 2]], jnp.int32),
                             jnp.asarray([0, 3], jnp.int32),
                             jnp.asarray([0, 2], jnp.int32))
            v_mix = KVView(bt, jnp.asarray([[5, -1, -1], [0, 1, 2]], jnp.int32),
                           jnp.asarray([6, 3], jnp.int32),
                           jnp.asarray([0, 2], jnp.int32))
            return v_pre_a, v_dec, v_pre_b, v_mix

        t_pre_a = jnp.asarray([[1, 2, 3, 4, 5], [0] * 5], jnp.int32)
        t_dec = jnp.asarray([[7], [0]], jnp.int32)
        t_pre_b = jnp.asarray([[0] * 3, [9, 8, 7]], jnp.int32)
        t_mix = jnp.asarray([[7, 0, 0], [9, 8, 7]], jnp.int32)

        def serve_logits(mode):
            nonlocal params_srv
            rt_s = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                           tp=TPConfig(mode=mode, chunks=2))
            m = build_model(cfg_srv, rt_s)
            if params_srv is None:
                params_srv = m.init(jax.random.key(31))
            v_pre_a, v_dec, v_pre_b, v_mix = serve_views()
            with sharding.use_mesh(mesh14):
                pools = m.init_pools(8, 4)
                _, pools = m.serve_step(params_srv, t_pre_a, pools, v_pre_a)
                lg_dec, _ = m.serve_step(params_srv, t_dec, pools, v_dec)
                lg_pre, _ = m.serve_step(params_srv, t_pre_b, pools, v_pre_b)
                lg_mix, _ = m.serve_step(params_srv, t_mix, pools, v_mix)
            return lg_dec, lg_pre, lg_mix

        for mode in ("barrier", "cais"):
            lg_dec, lg_pre, lg_mix = serve_logits(mode)
            err = max(float(jnp.abs(lg_mix[0] - lg_dec[0]).max()),
                      float(jnp.abs(lg_mix[1] - lg_pre[1]).max()))
            check(f"serve.mixed_vs_single.{mode}", err, 1e-6)

        ar_calls["n"] = 0
        register_backend(CountingCAIS())
        try:
            serve_logits("cais-count")
            # stack_step scans over periods, so the period graph traces ONCE
            # per serve_step shape: 2 gemm_ar dispatches (attention out-proj +
            # FFN down-proj) each for the S=5 prefill, the S=1 decode and the
            # S=3 chunk/mixed steps (the two S=3 steps may share a trace)
            check("serve.backend_dispatch_gemm_ar",
                  0.0 if ar_calls["n"] >= 6 else 1.0)
        finally:
            unregister_backend("cais-count")

        # ---------------- full model: auto == barrier == cais ----------------
        mesh2 = sharding.make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("deepseek-7b").smoke().scaled(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
            d_ff=128)
        tokens = jax.random.randint(jax.random.key(7), (4, 32), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "labels": tokens}
        key = jax.random.key(0)
        losses = {}
        for mode in ("auto", "barrier", "cais"):
            rt = Runtime(compute_dtype="float32", remat=(mode == "cais"),
                         loss_chunk=16, tp=TPConfig(mode=mode, chunks=2))
            model = build_model(cfg, rt)
            params = model.init(key)
            with sharding.use_mesh(mesh2):
                losses[mode] = float(jax.jit(model.loss)(params, batch))
        check("model.auto_vs_barrier", abs(losses["auto"] - losses["barrier"]))
        check("model.auto_vs_cais", abs(losses["auto"] - losses["cais"]))

        # cais grads finite under remat
        rt = Runtime(compute_dtype="float32", remat=True, loss_chunk=16,
                     tp=TPConfig(mode="cais", chunks=2))
        model = build_model(cfg, rt)
        params = model.init(key)
        with sharding.use_mesh(mesh2):
            loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
        ok = all(np.all(np.isfinite(np.asarray(g, np.float32)))
                 for g in jax.tree.leaves(grads))
        check("model.cais_grads_finite", 0.0 if ok else 1.0)

        # HLO structure: cais mode must contain collective-permutes and no
        # all-gather on the FFN path; barrier mode must contain all-gathers.
        def hlo_for(mode):
            rt = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                         tp=TPConfig(mode=mode, chunks=2))
            model = build_model(cfg, rt)
            params = model.init(key)
            with sharding.use_mesh(mesh2):
                return jax.jit(model.loss).lower(params, batch).compile().as_text()

        cais_hlo = hlo_for("cais")
        barrier_hlo = hlo_for("barrier")
        check("hlo.cais_has_permute",
              0.0 if "collective-permute" in cais_hlo else 1.0)
        check("hlo.barrier_has_allgather",
              0.0 if "all-gather" in barrier_hlo else 1.0)

        # ---------------- CAIS expert all-to-all (EP) --------------------------
        n, C, d, F = 8, 16, 32, 48
        send8 = jax.random.normal(jax.random.key(9), (8, n, C, d))
        wu8 = jax.random.normal(jax.random.key(10), (8, d, F)) * 0.1
        wd8 = jax.random.normal(jax.random.key(12), (8, F, d)) * 0.1

        def a2a(kind, bidir=True):
            def local(send, wu, wd):
                s, u, w = send[0], wu[0], wd[0]
                ffn = lambda t: jax.nn.gelu(t @ u) @ w
                if kind == "barrier":
                    return prim.barrier_a2a_expert_ffn(s, ffn, "model")[None]
                return prim.a2a_expert_ffn(
                    s, ffn, "model", CAISConfig(bidirectional=bidir))[None]
            return jax.jit(sharding.shard_map(
                local, mesh=mesh, in_specs=(P("model"), P("model"), P("model")),
                out_specs=P("model"), check_vma=False))(send8, wu8, wd8)

        ref_a2a = a2a("barrier")
        check("a2a_expert.cais", float(jnp.abs(a2a("cais") - ref_a2a).max()),
              1e-5)
        check("a2a_expert.cais_uni",
              float(jnp.abs(a2a("cais", bidir=False) - ref_a2a).max()), 1e-5)

        # MoE model: CE identical across modes (aux estimator partitioning
        # differs by design — isolate it)
        import dataclasses

        import repro.models.transformer as tr
        aux_w = tr.AUX_LOSS_WEIGHT
        tr.AUX_LOSS_WEIGHT = 0.0
        try:
            cfg_moe = get_arch("mixtral-8x7b").smoke().scaled(
                num_layers=2, d_model=64, num_heads=8, num_kv_heads=8,
                head_dim=16, d_ff=64, window=16)
            cfg_moe = cfg_moe.scaled(moe=dataclasses.replace(
                cfg_moe.moe, capacity_factor=8.0, group_size=1024))
            toks = jax.random.randint(jax.random.key(13), (2, 32), 0,
                                      cfg_moe.vocab_size)
            bmoe = {"tokens": toks, "labels": toks}
            ls = {}
            for mode in ("auto", "cais"):
                rt = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                             tp=TPConfig(mode=mode, chunks=2))
                mm = build_model(cfg_moe, rt)
                pp = mm.init(jax.random.key(0))
                with sharding.use_mesh(mesh2):
                    ls[mode] = float(jax.jit(mm.loss)(pp, bmoe))
            check("moe.auto_vs_cais_ce", abs(ls["auto"] - ls["cais"]), 2e-5)
        finally:
            tr.AUX_LOSS_WEIGHT = aux_w

        # ---------------- graph-built backward: train grads vs autodiff -------
        # Train-loss gradients routed through sp_period's graph-built custom VJP
        # (the backward is itself a dataflow graph; fwd+bwd merge for pass 3,
        # docs/training.md) must match plain JAX autodiff of the UNSPLIT forward
        # at 1e-6 on the 4-way ring, per backend, for dense / GQA / MoE — and
        # compose with remat (jax.checkpoint replays the period forward, then
        # re-enters the same graph VJP).
        cfg_gqa2 = cfg.scaled(num_kv_heads=2)

        def train_grads(cfg_, batch_, rt_):
            model_ = build_model(cfg_, rt_)
            params_ = model_.init(jax.random.key(0))
            with sharding.use_mesh(mesh2):
                _, grads_ = jax.jit(jax.value_and_grad(model_.loss))(
                    params_, batch_)
            return grads_

        def max_leaf_err(a, b):
            errs = jax.tree.map(
                lambda u, v: float(jnp.abs(u.astype(jnp.float32)
                                           - v.astype(jnp.float32)).max()), a, b)
            return max(jax.tree.leaves(errs))

        for label, cfg_g, batch_g, mb_g in (
                ("dense", cfg, batch, 2), ("gqa", cfg_gqa2, batch, 2),
                # MoE now rides the graph backward THROUGH the IR (route /
                # a2a_ffn / unroute adjoints with the aux cotangent seeded per
                # chain) — unsplit (mb=1) only because an explicit microbatch
                # split changes the aux statistic itself
                ("moe", cfg_moe, bmoe, 1)):
            for mode in ("barrier", "cais"):
                rt_graph = Runtime(
                    compute_dtype="float32", remat=False, loss_chunk=16,
                    tp=TPConfig(mode=mode, chunks=2, microbatches=mb_g,
                                graph_backward=True))
                rt_auto = Runtime(
                    compute_dtype="float32", remat=False, loss_chunk=16,
                    tp=TPConfig(mode=mode, chunks=2, graph_backward=False))
                err = max_leaf_err(train_grads(cfg_g, batch_g, rt_graph),
                                   train_grads(cfg_g, batch_g, rt_auto))
                check(f"train_grad.graph_vs_autodiff.{label}.{mode}", err, 1e-6)
        for mode in ("barrier", "cais"):
            rt_graph = Runtime(
                compute_dtype="float32", remat=True, loss_chunk=16,
                tp=TPConfig(mode=mode, chunks=2, microbatches=2,
                            graph_backward=True))
            rt_auto = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                              tp=TPConfig(mode=mode, chunks=2,
                                          graph_backward=False))
            err = max_leaf_err(train_grads(cfg, batch, rt_graph),
                               train_grads(cfg, batch, rt_auto))
            check(f"train_grad.graph_vs_autodiff.remat.{mode}", err, 1e-6)

        # decode/ragged train grads: the replicated-activation layout
        # (seq_sharded=False — S=1 decode and ragged S % tp != 0 shapes) now
        # has graph-path adjoints (gemm_col ⇒ grad allreduce through w^T,
        # gemm_ar ⇒ local dx/dw), so sp_block's graph-built custom VJP must
        # match the graph_backward=False autodiff of the SAME forward at 1e-6
        # per backend — graph_backward no longer silently excludes
        # decode-shaped periods.
        params_dgr = tr_mod.init_block(jax.random.key(27), "attn", cfg_blk,
                                       jnp.float32)
        x_full = jax.random.normal(jax.random.key(28), (2, 8, d), jnp.float32)
        for s_lab, s_len in (("s1", 1), ("ragged_s3", 3)):
            xs = x_full[:, :s_len]
            for mode in ("barrier", "cais"):
                def dec_grads(graph_bwd):
                    tpc_d = tp_mod.TPContext(mesh=mesh4, backend=mode,
                                             cais=cais4,
                                             graph_backward=graph_bwd)

                    def f(x_, p_):
                        out, _ = tp_mod.sp_block(tpc_d, x_, p_, cfg_blk, "attn",
                                                 seq_sharded=False)
                        # mean, not sum: keep the cotangent O(1) so the 1e-6
                        # absolute pin measures schedule parity, not loss scale
                        return jnp.mean(out * out)

                    return jax.jit(jax.grad(f, argnums=(0, 1)))(xs, params_dgr)

                check(f"train_grad.decode_gemm_ar.{s_lab}.{mode}",
                      max_leaf_err(dec_grads(True), dec_grads(False)), 1e-6)

        # dispatch-counter proof: the decode-layout backward allreduces run
        # through the backend (each gemm_col adjoint dispatches one backend
        # gemm_ar over the transposed weight), never through implicit psums.
        ar_bwd = {"n": 0}

        class CountingARCAIS(CAISBackend):
            name = "cais-count-ar"

            def gemm_ar(self, xl, wl, axis, cc):
                ar_bwd["n"] += 1
                return super().gemm_ar(xl, wl, axis, cc)

        register_backend(CountingARCAIS())
        try:
            tpc_cnt = tp_mod.TPContext(mesh=mesh4, backend="cais-count-ar",
                                       cais=cais4, graph_backward=True)

            def f_cnt(x_, p_):
                out, _ = tp_mod.sp_block(tpc_cnt, x_, p_, cfg_blk, "attn",
                                         seq_sharded=False)
                return jnp.sum(out * out)

            jax.grad(f_cnt)(x_full[:, :1], params_dgr)
            n_total = ar_bwd["n"]
            ar_bwd["n"] = 0
            tp_mod.sp_block(tpc_cnt, x_full[:, :1], params_dgr, cfg_blk, "attn",
                            seq_sharded=False)
            n_fwd = ar_bwd["n"]
        finally:
            unregister_backend("cais-count-ar")
        # backward trace = forward replay + ≥1 grad-allreduce per projection
        check("train_grad.decode_gemm_ar.backend_dispatch",
              0.0 if n_total > n_fwd >= 2 else 1.0)

        # ---------------- hierarchical 2D-mesh TP: flat ≡ tp_in × tp_out ------
        # Full-model loss + train grads on a tp_in=2 × tp_out=4 mesh (per-axis
        # collective composition, docs/topology.md) must match the flat 8-ring
        # at 1e-6 per backend. The MoE config carries E=8 experts so BOTH
        # meshes take the period-graph path: flat shards experts over the whole
        # ring (E % 8 == 0), the 2D mesh takes grouped EP — experts over the
        # slow tp_out axis only, replicated across tp_in.
        mesh_flat8 = sharding.make_mesh((1, 8), ("data", "model"))
        mesh_2d = sharding.make_tp_mesh(2, 4)
        cfg_moe8 = cfg_moe.scaled(moe=dataclasses.replace(
            cfg_moe.moe, num_experts=8))

        def loss_and_grads(cfg_, batch_, rt_, mesh_):
            model_ = build_model(cfg_, rt_)
            params_ = model_.init(jax.random.key(0))
            with sharding.use_mesh(mesh_):
                l_, g_ = jax.jit(jax.value_and_grad(model_.loss))(
                    params_, batch_)
            return float(l_), g_

        for label, cfg_t, batch_t in (("dense", cfg, batch),
                                      ("gqa", cfg_gqa2, batch),
                                      ("moe", cfg_moe8, bmoe)):
            for mode in ("barrier", "cais"):
                rt_t = Runtime(compute_dtype="float32", remat=False,
                               loss_chunk=16,
                               tp=TPConfig(mode=mode, chunks=2,
                                           graph_backward=True))
                l_flat, g_flat = loss_and_grads(cfg_t, batch_t, rt_t, mesh_flat8)
                l_2d, g_2d = loss_and_grads(cfg_t, batch_t, rt_t, mesh_2d)
                check(f"topo2d.{label}.{mode}", abs(l_flat - l_2d), 1e-6)
                check(f"topo2d.{label}.{mode}.train_grad",
                      max_leaf_err(g_flat, g_2d), 1e-6)

        # grouped-EP dispatch proof: on the 2D mesh the expert all-to-all must
        # only ever cross the slow tp_out axis — forward AND backward: the
        # hierarchical backend re-enters a2a_expert_ffn / grad_a2a_expert_ffn
        # with the concrete leg axis, so every non-composite axis the backend
        # sees must be tp_out (grouped-EP grads stay off the fast tp_in links).
        a2a_axes = []
        grad_a2a_axes = []

        class RecordingCAIS(CAISBackend):
            name = "cais-record"

            def a2a_expert_ffn(self, send, ffn, axis, cais):
                a2a_axes.append(axis)
                return super().a2a_expert_ffn(send, ffn, axis, cais)

            def grad_a2a_expert_ffn(self, send, gy, bwd_row, axis, cais):
                grad_a2a_axes.append(axis)
                return super().grad_a2a_expert_ffn(send, gy, bwd_row, axis,
                                                   cais)

        register_backend(RecordingCAIS())
        try:
            rt_rec = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                             tp=TPConfig(mode="cais-record", chunks=2,
                                         graph_backward=True))
            model_rec = build_model(cfg_moe8, rt_rec)
            params_rec = model_rec.init(jax.random.key(0))
            with sharding.use_mesh(mesh_2d):
                l_rec, g_rec = jax.jit(jax.value_and_grad(model_rec.loss))(
                    params_rec, bmoe)
                l_rec = float(l_rec)
        finally:
            unregister_backend("cais-record")
        rt_ref = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                         tp=TPConfig(mode="cais", chunks=2))
        model_ref = build_model(cfg_moe8, rt_ref)
        params_ref = model_ref.init(jax.random.key(0))
        with sharding.use_mesh(mesh_2d):
            l_ref, g_ref = jax.jit(jax.value_and_grad(model_ref.loss))(
                params_ref, bmoe)
            l_ref = float(l_ref)
        concrete = [a for a in a2a_axes if not isinstance(a, tuple)]
        concrete_g = [a for a in grad_a2a_axes if not isinstance(a, tuple)]
        check("grouped_ep.dispatch.parity", abs(l_rec - l_ref), 1e-6)
        check("grouped_ep.dispatch.parity.train_grad",
              max_leaf_err(g_rec, g_ref), 1e-6)
        check("grouped_ep.dispatch.tp_out_only",
              0.0 if (concrete
                      and all(a == sharding.TP_OUT_AXIS for a in concrete))
              else 1.0)
        # the backward a2a runs through the backend (dispatch-counter proof)
        # and its concrete legs stay off tp_in under grouped EP too
        check("grouped_ep.grad_dispatch.through_backend",
              0.0 if len(grad_a2a_axes) >= 1 else 1.0)
        check("grouped_ep.grad_dispatch.tp_out_only",
              0.0 if (concrete_g
                      and all(a == sharding.TP_OUT_AXIS for a in concrete_g))
              else 1.0)

        # ---------------- elastic resharding across meshes --------------------
        # Train 2 steps on a (2,4) mesh, checkpoint, restore onto (4,2) and
        # continue — losses must continue exactly (deliverable: elastic scaling).
        import tempfile

        from repro.checkpoint import store as ckpt_store
        from repro.configs.base import ShapeConfig
        from repro.data.pipeline import make_batch
        from repro.launch import specs as SP
        from repro.optim import constant_schedule, make_optimizer
        from repro.train.step import init_state, make_train_step

        cfg_e = get_arch("internlm2-1.8b").smoke()
        rt_e = Runtime(compute_dtype="float32", remat=False, loss_chunk=16)
        model_e = build_model(cfg_e, rt_e)
        opt_e = make_optimizer("adamw", constant_schedule(1e-3))
        step_e = jax.jit(make_train_step(model_e, opt_e, rt_e))
        shp = ShapeConfig("t", 16, 4, "train")

        def run_steps(state, mesh_, a, b):
            with sharding.use_mesh(mesh_):
                for s in range(a, b):
                    state, met = step_e(state, make_batch(cfg_e, shp, s))
            return state, float(met["loss"])

        mesh_a = sharding.make_mesh((2, 4), ("data", "model"))
        mesh_b = sharding.make_mesh((4, 2), ("data", "model"))

        st = init_state(model_e, opt_e, jax.random.key(0))
        st_ref = jax.tree.map(jnp.copy, st)
        # reference: 4 steps without interruption (no mesh)
        st_ref, loss_ref = run_steps(st_ref, None, 0, 4)

        st, _ = run_steps(st, mesh_a, 0, 2)
        with tempfile.TemporaryDirectory() as td:
            ckpt_store.save(td, st, step=2)
            template = jax.eval_shape(lambda: st)
            shapes = jax.eval_shape(lambda: st)
            sh_b = SP.state_shardings(cfg_e, mesh_b, shapes, rt_e)
            restored, _ = ckpt_store.restore(td, template)
            restored = jax.device_put(restored, sh_b)
        st2, loss2 = run_steps(restored, mesh_b, 2, 4)
        check("elastic.loss_continuity", abs(loss2 - loss_ref), 1e-4)

        # ---------------- int8 gradient compression (error feedback) ----------
        from repro.optim.compression import compressed_psum, init_error_feedback

        mesh_dp = sharding.make_mesh((8,), ("data",))
        gkey = jax.random.key(11)
        local_grads = jax.random.normal(gkey, (8, 64)) * jnp.linspace(
            0.1, 3.0, 8)[:, None]   # heterogeneous per-device grads
        exact_mean = jnp.mean(local_grads, axis=0)

        def dp_reduce(g, ef):
            return compressed_psum({"g": g}, {"g": ef}, axes=("data",))

        ef0 = jnp.zeros((1, 64))

        red, ef = jax.jit(sharding.shard_map(
            dp_reduce, mesh=mesh_dp,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)),
            check_vma=False))(local_grads, jnp.zeros_like(local_grads))
        # every replica holds the same reduced value, ≈ exact mean within int8
        approx = red["g"][0]
        rel = float(jnp.abs(approx - exact_mean).max()
                    / (jnp.abs(exact_mean).max() + 1e-9))
        check("compression.int8_close", rel, 0.05)

        # error feedback: repeated reduction of a CONSTANT gradient with EF must
        # average to the exact value (bias decays)
        acc = jnp.zeros((64,))
        ef_state = jnp.zeros_like(local_grads)
        for _ in range(16):
            red, new_ef = jax.jit(sharding.shard_map(
                dp_reduce, mesh=mesh_dp,
                in_specs=(P("data", None), P("data", None)),
                out_specs=(P("data", None), P("data", None)),
                check_vma=False))(local_grads, ef_state)
            ef_state = new_ef["g"]
            acc = acc + red["g"][0]
        rel_ef = float(jnp.abs(acc / 16 - exact_mean).max()
                       / (jnp.abs(exact_mean).max() + 1e-9))
        check("compression.error_feedback_unbiased", rel_ef, 0.01)

    if FAILED:
        print("FAILED:", FAILED)
        sys.exit(1)
    print("ALL OK")


if __name__ == "__main__":
    main(tuple(sys.argv[1:]) or PARTS)
