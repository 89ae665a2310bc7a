"""The program names its work in the compiled step and in the profiler's
host trace: every node ``dataflow.execute`` runs carries
``df.<op>.<node>``, the period's ``shard_map`` bodies ``tp.period``, the
model ``model.embed`` / ``model.head``, the train step
``step.optimizer`` (``jax.named_scope``, read back from the compiled
HLO's ``op_name`` metadata); ``Trainer.run`` marks each step's host
phases ``train.batch``, ``train.dispatch`` and ``train.sync``
(``jax.profiler.TraceAnnotation``) and keeps their times in its
history."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro import sharding
from repro.configs import ShapeConfig, get_arch
from repro.core import dataflow as df
from repro.core import tp
from repro.core.primitives import CAISConfig

def _period(n_blocks=1, mb=2):
    """A dense one-block period at toy widths, the graph ``sp_period``
    builds for it (split into ``mb`` microbatch chains, optimized), its
    training graph, weights and a one-device mesh context."""
    import repro.models.transformer as tr

    mesh = sharding.make_mesh((1, 1), ("data", "model"))
    tpc = tp.TPContext(mesh=mesh, backend="cais",
                       cais=CAISConfig(num_chunks=1))
    cfg = get_arch("deepseek-7b").smoke().scaled(
        num_layers=n_blocks, d_model=32, num_heads=4, num_kv_heads=4,
        head_dim=8, d_ff=48)
    kinds = ("attn",) * n_blocks
    ps = [tr.init_block(jax.random.key(60 + i), k, cfg, jnp.float32)
          for i, k in enumerate(kinds)]
    base, weights, _, _ = tp._period_graph(tpc, ps, cfg, kinds)
    merged = tp.microbatch_period_graph(base, mb)
    g2 = df.fuse_sublayer_chain(df.fuse_shared_gather(
        df.fuse_compute_aware(merged)))
    tg = df.build_training_graph(g2, norm=cfg.norm)
    return df.optimize(merged), df.optimize(tg.graph), tg, weights, mesh


def _names(nodes):
    return [f"df.{n.op}.{n.name}" for n in nodes if n.op != "input"]


def _live(graph):
    """The nodes some graph output depends on (JAX drops the rest)."""
    need, live = set(graph.outputs), []
    for n in reversed(graph.nodes):
        if set(n.outputs) & need:
            live.append(n)
            need.update(n.inputs)
    return live


def _inputs(graph, weights, x):
    vals = {n.name: x for n in graph.nodes if n.op == "input"}
    return vals, df.derived_weights(graph, dict(weights))


@pytest.mark.parametrize("where", ["jit", "shard_map_cais"])
def test_execute_names_every_node(where):
    """A two-chain dense block and its training graph through
    ``execute()`` under ``jit`` (no axis), or in ``shard_map`` over a
    one-device ring with every fused collective dispatched to the
    ``cais`` backend: every node of the forward graph names its ops in
    the compiled text; every node the training graph's outputs depend on
    names its ops in the lowered module, and each kind of them survives
    compilation (a one-device ring lets XLA merge equal twins)."""
    from jax.sharding import PartitionSpec as P

    fwd, bwd, _, weights, mesh = _period()
    assert {"overlap_asym", "fused_rs_ln_ag_multi"} <= {n.op
                                                        for n in fwd.nodes}
    assert {"bwd_ag_gemm", "overlap_asym"} <= {n.op for n in bwd.nodes}
    # without an axis a raw collective node is the identity: no op to name
    identity = {"allgather", "reduce_scatter", "allreduce"} \
        if where == "jit" else set()
    x = jax.random.normal(jax.random.key(8), (4, 16, 32), jnp.float32)
    for graph in (fwd, bwd):
        vals, ws = _inputs(graph, weights, x)
        if where == "jit":
            f = jax.jit(lambda v, w, g=graph: df.execute(g, v, w))
        else:
            def local(v, w, g=graph):
                return df.execute(g, v, w, axis="model", backend="cais",
                                  cais=CAISConfig(num_chunks=1))

            f = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                      out_specs=P(), check_vma=False))
        lowered = f.lower(vals, ws)
        text = lowered.compile().as_text()
        if graph is fwd:
            assert [s for s in _names(graph.nodes) if s not in text] == []
            continue
        pre = lowered.as_text(debug_info=True)
        live = [n for n in _live(graph) if n.op not in identity]
        assert [s for s in _names(live) if s not in pre] == []
        kinds = {n.op for n in live} - {"input"}
        assert {k for k in kinds if f"/df.{k}." not in text} == set()


@pytest.fixture(scope="module")
def trainer():
    """A two-layer deepseek-7b smoke trainer on a one-device TP mesh with
    the ``cais`` backend, as the benchmark's ring builds it."""
    from repro.data.pipeline import DataConfig
    from repro.launch import specs
    from repro.models import build_model
    from repro.optim import constant_schedule, make_optimizer
    from repro.train import Trainer, TrainerConfig

    arch = get_arch("deepseek-7b").smoke().scaled(num_layers=2)
    rt = specs.runtime_for(arch, tp_mode="cais")
    mesh = sharding.make_mesh((1, 1), ("data", "model"))
    model = build_model(arch, rt)
    opt = make_optimizer("adamw", constant_schedule(1e-3))
    return Trainer(model, opt, arch, ShapeConfig("t", 32, 2, "train"), rt,
                   TrainerConfig(total_steps=0, log_every=10 ** 9),
                   DataConfig(seed=3), mesh=mesh)


def test_sp_period_grad_carries_tp_period():
    """``sp_period``'s forward and custom-VJP backward ``shard_map`` bodies
    run under ``tp.period``, the graph nodes inside it."""
    import repro.models.transformer as tr

    mesh = sharding.make_mesh((1, 1), ("data", "model"))
    tpc = tp.TPContext(mesh=mesh, backend="cais",
                       cais=CAISConfig(num_chunks=1), num_microbatches=2)
    cfg = get_arch("deepseek-7b").smoke().scaled(
        num_layers=1, d_model=32, num_heads=4, num_kv_heads=4, head_dim=8,
        d_ff=48)
    params = tr.init_block(jax.random.key(11), "attn", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(12), (2, 16, 32), jnp.float32)

    def loss(x_, p_):
        out, _ = tp.sp_period(tpc, x_, (p_,), cfg, ("attn",),
                              norm_kind=cfg.norm)
        return jnp.sum(out * out)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, params).compile().as_text()
    fwd = [ln for ln in text.splitlines() if "/tp.period/df." in ln
           and "transpose(" not in ln]
    bwd = [ln for ln in text.splitlines() if "/tp.period/df.bwd_ag_gemm."
           in ln and "transpose(" in ln]
    assert fwd and bwd


def test_train_step_carries_program_scopes(trainer):
    """Embedding, head and optimizer scopes in the compiled step, through
    ``jvp`` and ``transpose``; one device takes no ``shard_map`` path."""
    with sharding.use_mesh(trainer.mesh):
        state = trainer.fresh_state(0)
        tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        text = trainer.train_step.lower(
            state, {"tokens": tok, "labels": tok}).compile().as_text()
    for scope in ("jvp(model.embed)", "transpose(jvp(model.embed))",
                  "jvp(model.head)", "transpose(jvp(model.head))",
                  "/step.optimizer/"):
        assert scope in text, scope


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events
                           if ev.name.startswith("train."))
    return sorted(out)


def test_trainer_records_host_phases(trainer, tmp_path):
    state = trainer.fresh_state(0)
    trainer.cfg.total_steps = 1
    state = trainer.run(state)
    trainer.cfg.total_steps = 3
    with jax.profiler.trace(str(tmp_path)):
        trainer.run(state)
    evs = _host_events(str(tmp_path))
    assert [n for _, _, n in evs] == \
        ["train.batch", "train.dispatch", "train.sync"] * 2
    for (_, end, _), (start, _, _) in zip(evs, evs[1:]):
        assert end <= start
    for h in trainer.history:
        parts = h["batch_s"] + h["dispatch_s"] + h["sync_s"]
        assert min(h["batch_s"], h["dispatch_s"], h["sync_s"]) >= 0
        assert parts == pytest.approx(h["dt"], rel=1e-9, abs=1e-12)
