"""Paper Fig. 12 — sub-layer (L1–L4) speedups of CAIS over each baseline,
plus measured cells on an 8-virtual-device ring (subprocess — the parent
keeps one device): the whole-block dataflow graph (``sp_block``, one
shard_map, pass-2 seam fusion) against the PR-1 per-sub-layer composition
(``sp_attention`` + ``sp_ffn``), the period-level graph (``sp_period``,
2 blocks in ONE shard_map with the cross-block seam fused) against the
per-block ``sp_block`` composition, and the microbatch-split period
(``num_microbatches=2`` — two independent chains in one graph, pass-3
``overlap_asym`` across them) against the unsplit serialized period, and
the perfsim-planned period (``planner="perfsim"``, docs/planner.md)
against the same split period under the greedy planner, and the
graph-built backward (``TPConfig.graph_backward`` — the ``sp_period``
custom VJP, docs/training.md) against plain JAX autodiff of the executed
forward. With ``$REPRO_BENCH_JSON`` set, every row (including the
subprocess cells) is dumped as the JSON baseline the CI slow-suite
commits as ``BENCH_pr10.json`` — a ``meta.sublayer_env`` row records the shapes/mode
so baselines regenerated under different settings are not silently
compared. Measured cells run on CPU-emulated virtual devices, where
``collective_permute`` chains serialize (no real bidirectional links), so
wall-clock "speedups" there are informational — the overlap cells are the
hook for real-hardware runs, and the perfsim Fig. 12 rows model the paper's
hardware."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import dump_rows_json, emit, record
from repro.core import perfsim as ps

_CHILD = "_REPRO_SUBLAYER_BLOCK_CHILD"


def _block_child() -> None:
    import jax
    import jax.numpy as jnp

    import repro.models.transformer as tr
    from benchmarks.common import bench_tiny, time_fn
    from repro import sharding
    from repro.configs import get_arch
    from repro.core import tp as tp_mod
    from repro.core.primitives import CAISConfig

    mesh = sharding.make_mesh((1, 8), ("data", "model"))
    S, d, d_ff = (256, 128, 256) if bench_tiny() else (1024, 256, 512)
    cfg = get_arch("deepseek-7b").smoke().scaled(
        num_layers=1, d_model=d, num_heads=8, num_kv_heads=8,
        head_dim=d // 8, d_ff=d_ff)
    params = tr.init_block(jax.random.key(0), "attn", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, S, d), jnp.float32)

    params2 = [tr.init_block(jax.random.key(i), "attn", cfg, jnp.float32)
               for i in (0, 2)]

    for mode in ("barrier", "cais"):
        tpc = tp_mod.TPContext(mesh=mesh, backend=mode,
                               cais=CAISConfig(num_chunks=2))

        fused = jax.jit(
            lambda x, tpc=tpc: tp_mod.sp_block(tpc, x, params, cfg,
                                               "attn")[0])

        def split(x, tpc=tpc):
            p, m, f = params, params["mixer"], params["ffn"]
            r1 = x + tp_mod.sp_attention(
                tpc, x, p["norm1"]["scale"], m["wq"], m["wk"], m["wv"],
                m["wo"], cfg)
            return r1 + tp_mod.sp_ffn(
                tpc, r1, p["norm2"]["scale"], f["w_up"], f.get("w_gate"),
                f["w_down"], cfg.act)

        t_fused = time_fn(fused, x)
        t_split = time_fn(jax.jit(split), x)
        emit(f"block.fused_vs_split.{mode}", t_fused,
             f"split_us={t_split:.0f} speedup={t_split / t_fused:.2f}x")

        # period-level graph (2 blocks, ONE shard_map, cross-block pass-2
        # seam fusion) vs the per-block sp_block composition
        period = jax.jit(
            lambda x, tpc=tpc: tp_mod.sp_period(
                tpc, x, params2, cfg, ("attn", "attn"))[0])

        def per_block(x, tpc=tpc):
            for p in params2:
                x, _ = tp_mod.sp_block(tpc, x, p, cfg, "attn")
            return x

        t_period = time_fn(period, x)
        t_pb = time_fn(jax.jit(per_block), x)
        emit(f"period.graph_vs_perblock.{mode}", t_period,
             f"perblock_us={t_pb:.0f} speedup={t_pb / t_period:.2f}x")

        # microbatch-split period (2 independent chains in ONE graph, pass 3
        # cross-pairs their RS/AG into overlap_asym) vs the same period
        # unsplit (straight line — fully serialized after pass-2 fusion)
        split2 = jax.jit(
            lambda x, tpc=tpc: tp_mod.sp_period(
                tpc, x, params2, cfg, ("attn", "attn"),
                num_microbatches=2)[0])
        t_split2 = time_fn(split2, x)
        emit(f"period.split_vs_unsplit.{mode}", t_split2,
             f"unsplit_us={t_period:.0f} speedup={t_period / t_split2:.2f}x")

        # perfsim-planned period (planner="perfsim": the pass-3 pairing
        # and chunking come from the simulated-makespan search, memoized in
        # the plan cache under reports/plans/ — the artifact the 8-device CI
        # job uploads) vs the same split period under the greedy planner
        tpc_p = tp_mod.TPContext(mesh=mesh, backend=mode,
                                 cais=CAISConfig(num_chunks=2),
                                 planner="perfsim")
        planned = jax.jit(
            lambda x, tpc=tpc_p: tp_mod.sp_period(
                tpc, x, params2, cfg, ("attn", "attn"),
                num_microbatches=2)[0])
        t_planned = time_fn(planned, x)
        emit(f"planner.perfsim_vs_greedy.{mode}", t_planned,
             f"greedy_us={t_split2:.0f} speedup={t_split2 / t_planned:.2f}x")

        # graph-built backward (TPConfig.graph_backward — sp_period's custom
        # VJP lowers the backward as a dataflow graph merged with the
        # forward, docs/training.md) vs JAX autodiff of the executed
        # forward graph, on a grad-of-sum-of-squares train-step proxy
        import dataclasses as _dc

        def grad_fn(tpc_):
            def loss(x, ps_):
                out, _ = tp_mod.sp_period(tpc_, x, ps_, cfg,
                                          ("attn", "attn"),
                                          num_microbatches=2)
                return jnp.sum(out * out)
            return jax.jit(jax.grad(loss, argnums=(0, 1)))

        t_graph = time_fn(grad_fn(tpc), x, params2)
        t_auto = time_fn(grad_fn(_dc.replace(tpc, graph_backward=False)),
                         x, params2)
        emit(f"train_step.graph_vs_autodiff.{mode}", t_graph,
             f"autodiff_us={t_auto:.0f} speedup={t_auto / t_graph:.2f}x")

        # hierarchical 2D-mesh TP (docs/topology.md): the same 1-block graph
        # on a tp_in × tp_out = 2 × 4 mesh (per-axis collective composition)
        # vs the flat 8-ring. The barrier row feeds the inter-tier
        # (bw2, alpha2) calibration fit (repro.plan.calibrate.TOPO_CELLS).
        tpc2d = tp_mod.TPContext(mesh=sharding.make_tp_mesh(2, 4),
                                 backend=mode, cais=CAISConfig(num_chunks=2))
        fused2d = jax.jit(
            lambda x, tpc=tpc2d: tp_mod.sp_block(tpc, x, params, cfg,
                                                 "attn")[0])
        t_2d = time_fn(fused2d, x)
        emit(f"topo.flat_vs_2d.{mode}", t_2d,
             f"flat_us={t_fused:.0f} ratio={t_2d / t_fused:.2f}x")

    # grouped-EP MoE (E < tp): experts sharded over tp_out only, all-to-all
    # never crossing tp_in — vs the flat ring's replicated-expert fallback
    cfg_moe = get_arch("mixtral-8x7b").smoke().scaled(
        num_layers=1, d_model=d, num_heads=8, num_kv_heads=8,
        head_dim=d // 8, d_ff=d_ff, window=16)
    params_moe = tr.init_block(jax.random.key(3), "attn", cfg_moe,
                               jnp.float32)
    moe_ts = {}
    for label, mesh_m in (("grouped_ep", sharding.make_tp_mesh(2, 4)),
                          ("flat_tp", mesh)):
        tpc_m = tp_mod.TPContext(mesh=mesh_m, backend="cais",
                                 cais=CAISConfig(num_chunks=2))
        fn = jax.jit(lambda x, tpc=tpc_m: tp_mod.sp_moe_ffn(
            tpc, x, params_moe["norm2"]["scale"], params_moe["ffn"],
            cfg_moe)[0])
        moe_ts[label] = time_fn(fn, x)
    emit("moe.grouped_ep_vs_tp", moe_ts["grouped_ep"],
         f"flat_us={moe_ts['flat_tp']:.0f} "
         f"ratio={moe_ts['grouped_ep'] / moe_ts['flat_tp']:.2f}x")

    # MoE train step through the graph-built backward (route / a2a_ffn /
    # unroute adjoints with the aux cotangent, docs/training.md) vs JAX
    # autodiff of the executed forward, with an explicit 2-microbatch split
    # so pass 3 can pair one chain's backward grad-a2a/grad-RS against the
    # other chain's forward gathers (cross-direction overlap_asym). E=8 so
    # the flat 8-ring takes the period-graph MoE path (E % ring == 0).
    import dataclasses as _dc

    cfg_moe = cfg_moe.scaled(moe=_dc.replace(cfg_moe.moe, num_experts=8))
    params_moe = tr.init_block(jax.random.key(5), "attn", cfg_moe,
                               jnp.float32)
    for mode in ("barrier", "cais"):
        tpc_m = tp_mod.TPContext(mesh=mesh, backend=mode,
                                 cais=CAISConfig(num_chunks=2))

        def moe_grad_fn(tpc_):
            def loss(x, p):
                out, aux = tp_mod.sp_period(tpc_, x, [p], cfg_moe,
                                            ("attn",), num_microbatches=2)
                return jnp.sum(out * out) + jnp.sum(aux)
            return jax.jit(jax.grad(loss, argnums=(0, 1)))

        t_g = time_fn(moe_grad_fn(tpc_m), x, params_moe)
        t_a = time_fn(moe_grad_fn(_dc.replace(tpc_m, graph_backward=False)),
                      x, params_moe)
        emit(f"train_step.moe_graph_vs_autodiff.{mode}", t_g,
             f"autodiff_us={t_a:.0f} speedup={t_a / t_g:.2f}x")


def run() -> None:
    if os.environ.get(_CHILD):
        _block_child()
        dump_rows_json()        # child rows → the path the parent hands us
        return
    # measured cell first (subprocess owns the 8-device override). The
    # child dumps its rows as JSON to a temp path; the parent merges them so
    # dump_rows_json() ($REPRO_BENCH_JSON) covers the measured cells too.
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD] = "1"
    env.setdefault("PYTHONPATH", "src")
    with tempfile.TemporaryDirectory() as td:
        env["REPRO_BENCH_JSON"] = os.path.join(td, "child-rows.json")
        out = subprocess.run(
            [sys.executable, "-c",
             "from benchmarks.sublayer import run; run()"],
            capture_output=True, text=True, env=env, timeout=1200)
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            sys.stderr.write(out.stderr[-2000:])
            raise RuntimeError("fused-block bench failed")
        with open(env["REPRO_BENCH_JSON"]) as fh:
            for row in json.load(fh):
                record(row["name"], row["us_per_call"], row["derived"])

    # provenance row: which shapes/platform produced these numbers, so a
    # committed baseline regenerated under other settings is identifiable
    import jax

    from benchmarks.common import bench_tiny
    emit("meta.sublayer_env", 0.0,
         f"tiny={int(bench_tiny())} jax={jax.__version__} "
         f"platform={jax.default_backend()} "
         "note=measured-cells-cpu-emulated-informational")

    f = ps.calibrated_fabric()
    for cfg in ps.PAPER_MODELS:
        for which in ("L1", "L2", "L3", "L4"):
            t_cais, _ = ps.run_sublayer(cfg, ps.BASELINES["CAIS"], f, which)
            for name in ("TP-NVLS", "SP-NVLS", "CoCoNet", "FuseLib", "T3",
                         "CoCoNet-NVLS", "FuseLib-NVLS", "T3-NVLS", "LADM",
                         "CAIS-Base"):
                t, _ = ps.run_sublayer(cfg, ps.BASELINES[name], f, which)
                emit(f"fig12.{cfg.name}.{which}.CAIS_over_{name}",
                     t_cais * 1e6, f"speedup={t / t_cais:.2f}x")
    dump_rows_json()


if __name__ == "__main__":
    run()
