"""Property-based grad-parity harness for the CLOSED backward vocabulary
(ISSUE 10): every op the model path can leave in a post-pass-2 period graph
— MoE routing (``route``/``a2a_ffn``/``unroute``, aux-loss side-output
included), the replicated-activation decode/ragged layout
(``gemm_col``/``gemm_ar``, S=1 included) — has a declared adjoint whose
graph-built backward matches ``jax.vjp`` of the UNOPTIMIZED forward graph
to 1e-6, with ``optimize()`` both off and on.

All on the single-device reference path (``axis=None`` — collectives are
identity), swept by ``hypothesis`` strategies over expert count,
capacity factor, a2a ring factorization (the ring dim of the mesh the
graph is built for: 1×8 → ring 8, 2×4 → ring 2 grouped EP, 8×1 → ring 1;
mesh-free runs execute the per-owner LOCAL view, so expert weights carry
the E_loc = E/ring shard shape), ragged sequence lengths down to S=1, and
microbatch count. True multi-device parity for the same cells lives in
``multidev_checks.py`` (``train_grad.graph_vs_autodiff.moe.*``,
``train_grad.decode_gemm_ar.*``).
"""
import types

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.configs.base import MoEConfig
from repro.core import dataflow as df
from repro.core import tp

ATOL = 1e-6


def _toy_core(q, k, v):
    return q * jax.nn.sigmoid(k) + v


def _pass2(g):
    return df.fuse_sublayer_chain(df.fuse_shared_gather(
        df.fuse_compute_aware(g)))


def _moe_fns(E, cap, ring, has_gate=True, act="silu"):
    cfg = types.SimpleNamespace(
        act=act, moe=MoEConfig(num_experts=E, top_k=2, capacity_factor=cap))
    return tp._moe_graph_fns(cfg, ring, has_gate)


def _graph_grads(g2, weights, vals, gys, optimize=False):
    tg = df.build_training_graph(g2, norm="rmsnorm")
    bwd = df.optimize(tg.graph) if optimize else tg.graph
    env = dict(vals)
    env.update(dict(zip(tg.grad_inputs, gys)))
    res = df.execute(bwd, env, df.derived_weights(bwd, weights))
    got = dict(zip(bwd.outputs, res))
    dx = {v: got[g_] for v, g_ in tg.dx.items()}
    dw = {}
    for k, parts in tg.dweights.items():
        acc = got[parts[0]]
        for p_ in parts[1:]:
            acc = acc + got[p_]
        dw[k] = acc
    return dx, dw


def _ref_grads(g, weights, vals, gys):
    names = sorted(vals)

    def f(xs, w):
        return tuple(df.execute(g, dict(zip(names, xs)), w))

    _, pull = jax.vjp(f, tuple(vals[k] for k in names), weights)
    dxs, dw = pull(tuple(gys))
    return dict(zip(names, dxs)), dw


def _check(g, g2, weights, vals):
    """graph-built backward of g2 ≡ jax.vjp of the unoptimized g, ≤1e-6,
    with the training graph optimize()d both off and on."""
    outs = df.execute(g, vals, weights)
    gys = [jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                   ).reshape(o.shape).astype(o.dtype) * 0.3 for o in outs]
    dx_r, dw_r = _ref_grads(g, weights, vals, gys)
    for optimize in (False, True):
        dx_g, dw_g = _graph_grads(g2, weights, vals, gys, optimize=optimize)
        for k in dx_g:
            np.testing.assert_allclose(
                np.asarray(dx_g[k]), np.asarray(dx_r[k]), atol=ATOL,
                err_msg=f"dx[{k}] opt={optimize}")
        for k in weights:
            np.testing.assert_allclose(
                np.asarray(dw_g[k]), np.asarray(dw_r[k]), atol=ATOL,
                err_msg=f"dw[{k}] opt={optimize}")


def _key(*ints):
    k = jax.random.key(20)
    for i in ints:
        k = jax.random.fold_in(k, i)
    return k


# ---------------------------------------------------------------------------
# per-op adjoints
# ---------------------------------------------------------------------------


@given(E=st.sampled_from([2, 4]), cap=st.floats(1.0, 2.0),
       S=st.sampled_from([1, 5]))
@settings(deadline=None)
def test_adjoint_route(E, cap, S):
    """route ⇒ jax.vjp of the routing closure: the combine-weighted grad
    scatter AND the aux-loss statistic's cotangent feeding the router
    logits (through the differentiable density_proxy only — the one-hot
    density factor is piecewise-constant, so this IS the straight-through
    convention)."""
    d = 8
    route_fn, _, _ = _moe_fns(E, cap, ring=1)
    g = df.Graph([df.Node("x", "input"),
                  df.Node("rt", "route", ("x",), ("router",),
                          outputs=("send", "combine", "aux"), fn=route_fn)],
                 ("send", "combine", "aux"))
    w = {"router": jax.random.normal(_key(0, E), (d, E),
                                     jnp.float32) * 0.5}
    x = jax.random.normal(_key(1, E, S), (2, S, d), jnp.float32)
    _check(g, g, w, {"x": x})


@given(E=st.sampled_from([2, 4]), ring=st.sampled_from([1, 2]),
       gate=st.booleans())
@settings(deadline=None)
def test_adjoint_a2a_ffn(E, ring, gate):
    """a2a_ffn ⇒ bwd_a2a_ffn: per-row VJP of the expert FFN with
    expert-weight grads summed over the ring rows (the reference-path
    analogue of keeping dw on the owner). ring>1 runs the per-owner local
    view: E_loc = E/ring experts per row, shard-shaped weights."""
    d, f, cap = 6, 10, 3
    E_loc = E // ring
    _, expert_fn, _ = _moe_fns(E, 1.5, ring, has_gate=gate)
    wk = ("w_up",) + (("w_gate",) if gate else ()) + ("w_down",)
    g = df.Graph([df.Node("send", "input"),
                  df.Node("eout", "a2a_ffn", ("send",), wk, fn=expert_fn)],
                 ("eout",))
    w = {"w_up": jax.random.normal(_key(2, E, ring), (E_loc, d, f)) * 0.3,
         "w_down": jax.random.normal(_key(3, E, ring), (E_loc, f, d)) * 0.3}
    if gate:
        w["w_gate"] = jax.random.normal(_key(4, E, ring),
                                        (E_loc, d, f)) * 0.3
    send = jax.random.normal(_key(5, E, ring), (ring, E_loc * cap, d))
    _check(g, g, w, {"send": send})


@given(E=st.sampled_from([2, 4]), cap=st.floats(1.0, 2.0))
@settings(deadline=None)
def test_adjoint_unroute(E, cap):
    """unroute ⇒ the route adjoint's dual: cotangents scatter back through
    the combine weights into both the expert outputs and the combine tensor
    (xn is shape-only — its cotangent is exactly zero)."""
    d, S = 8, 4
    route_fn, _, unroute_fn = _moe_fns(E, cap, ring=1)
    g = df.Graph([df.Node("xn", "input"),
                  df.Node("rt", "route", ("xn",), ("router",),
                          outputs=("send", "combine", "aux"), fn=route_fn),
                  df.Node("eout", "input"),
                  df.Node("y", "unroute", ("eout", "combine", "xn"),
                          fn=unroute_fn)],
                 ("y", "aux"))
    w = {"router": jax.random.normal(_key(6, E), (d, E), jnp.float32) * 0.5}
    xn = jax.random.normal(_key(7, E), (2, S, d), jnp.float32)
    T = 2 * S
    capn = max(1, int(T * 2 / E * cap))
    eout = jax.random.normal(_key(8, E), (1, E * capn, d), jnp.float32)
    _check(g, g, w, {"xn": xn, "eout": eout})


@given(S=st.sampled_from([1, 3, 6]), gate=st.booleans())
@settings(deadline=None)
def test_adjoint_decode_block(S, gate):
    """The sequence_parallel=False (replicated-activation decode/ragged)
    layout: pass 1 leaves raw gemm_col and fuses gemm_row+allreduce into
    gemm_ar — both now in the adjoint vocabulary, S=1 included, so
    graph_backward no longer silently excludes decode-shaped periods."""
    d, f = 8, 12
    nodes, out = tp._dense_block_nodes(_toy_core, gate, "silu",
                                       seq_sharded=False)
    g = df.Graph([df.Node("x", "input")] + nodes, (out,))
    g2 = _pass2(g)
    assert any(n.op == "gemm_ar" for n in g2.nodes)
    assert any(n.op == "gemm_col" for n in g2.nodes)
    w = {"scale1": jax.random.normal(_key(9, S), (d,)) * 0.1 + 1.0,
         "scale2": jax.random.normal(_key(10, S), (d,)) * 0.1 + 1.0,
         "w_up": jax.random.normal(_key(11, S), (d, f)) * 0.3,
         "w_down": jax.random.normal(_key(12, S), (f, d)) * 0.3}
    for i, kk in enumerate(("wq", "wk", "wv", "wo")):
        w[kk] = jax.random.normal(_key(13 + i, S), (d, d)) * 0.3
    if gate:
        w["w_gate"] = jax.random.normal(_key(17, S), (d, f)) * 0.3
    x = jax.random.normal(_key(18, S), (2, S, d))
    _check(g, g2, w, {"x": x})


# ---------------------------------------------------------------------------
# whole-period property: MoE block graph, optimize off+on, microbatched
# ---------------------------------------------------------------------------


def _moe_block_setup(E, cap, ring, S, key0):
    d, f = 8, 12
    E_loc = E // ring
    route_fn, expert_fn, unroute_fn = _moe_fns(E, cap, ring)
    g = tp.moe_block_graph(_toy_core, route_fn, expert_fn, unroute_fn,
                           ("w_up", "w_gate", "w_down"), True)
    w = {"scale1": jax.random.normal(_key(key0, 0), (d,)) * 0.1 + 1.0,
         "scale2": jax.random.normal(_key(key0, 1), (d,)) * 0.1 + 1.0,
         "router": jax.random.normal(_key(key0, 2), (d, E),
                                     jnp.float32) * 0.5,
         "w_up": jax.random.normal(_key(key0, 3), (E_loc, d, f)) * 0.3,
         "w_gate": jax.random.normal(_key(key0, 4), (E_loc, d, f)) * 0.3,
         "w_down": jax.random.normal(_key(key0, 5), (E_loc, f, d)) * 0.3}
    for i, kk in enumerate(("wq", "wk", "wv", "wo")):
        w[kk] = jax.random.normal(_key(key0, 6 + i), (d, d)) * 0.3
    x = jax.random.normal(_key(key0, 10), (2, S, d), jnp.float32)
    return g, w, x


@given(E=st.sampled_from([2, 4]), cap=st.floats(1.0, 2.0),
       ring=st.sampled_from([1, 2]), S=st.sampled_from([1, 4]),
       mb=st.sampled_from([1, 2]))
@settings(deadline=None, max_examples=24)
def test_moe_period_grad_parity(E, cap, ring, S, mb):
    """Whole MoE period (attention + route → a2a_ffn → unroute, pass-2
    fused_rs_ln seam included): dx + every dw + the aux cotangent through
    the graph-built backward ≡ jax.vjp of the unoptimized graph, swept
    over expert count × capacity factor × ring factorization × ragged S
    (S=1 included) × microbatch count, optimize() off AND on."""
    g, w, x = _moe_block_setup(E, cap, ring, S, key0=30 + mb)
    base = g
    vals = {"x": x}
    if mb > 1:
        g = tp.microbatch_period_graph(base, mb)
        vals = {f"mb{i}.x": jax.random.normal(_key(40 + i, E, ring, S),
                                              (1, S, 8), jnp.float32)
                for i in range(mb)}
    g2 = _pass2(g)
    assert any(n.op == "fused_rs_ln" for n in g2.nodes)
    assert any(n.op == "a2a_ffn" for n in g2.nodes)
    _check(g, g2, w, vals)


def test_moe_training_graph_structure():
    """The merged fwd+bwd MoE graph is ONE graph: the a2a_ffn adjoint is a
    first-class bwd_a2a_ffn node carrying the expert weights, the route
    adjoint consumes the aux cotangent seed, and supports_backward says so."""
    g, _, _ = _moe_block_setup(4, 1.5, 1, 4, key0=50)
    g2 = _pass2(g)
    assert df.supports_backward(g2)
    tg = df.build_training_graph(g2, norm="rmsnorm")
    assert "d.aux" in tg.grad_inputs
    bwd = [n for n in tg.graph.nodes if n.op == "bwd_a2a_ffn"]
    assert len(bwd) == 1
    assert bwd[0].weights == ("w_up", "w_gate", "w_down")
    # every expert weight has a gradient group
    for k in ("w_up", "w_gate", "w_down", "router"):
        assert k in tg.dweights, sorted(tg.dweights)


def _bwd_component(name):
    return "adj." in name or name.startswith(("d.", "dsum", "dcat.",
                                              "dfull.", "dznorm.", "dz.",
                                              "xg.", "zg.", "znr."))


def test_moe_cross_direction_overlap_asym():
    """Acceptance: the optimized merged fwd+bwd graph of a 2-microbatch MoE
    period contains ≥1 overlap_asym spanning a FORWARD node of one chain and
    a BACKWARD node of the other — the planner can overlap mb1's backward
    grad collectives against mb0's forward gathers."""
    g, _, _ = _moe_block_setup(4, 1.5, 1, 4, key0=70)
    g2 = _pass2(tp.microbatch_period_graph(g, 2))
    tg = df.build_training_graph(g2, norm="rmsnorm")
    opt = df.optimize(tg.graph)
    pairs = [n for n in opt.nodes if n.op == "overlap_asym"]
    assert pairs, [(n.name, n.op) for n in opt.nodes]
    cross = [n for n in pairs
             if len({_bwd_component(s) for s in n.name.split("+")}) == 2]
    assert cross, [n.name for n in pairs]


# ---------------------------------------------------------------------------
# fallback gate: warn once naming the offending ops, stay parity-exact
# ---------------------------------------------------------------------------


def _moe_mini_setup():
    import dataclasses as _dc

    import repro.models.transformer as tr
    from repro import sharding
    from repro.configs import get_arch
    from repro.core.primitives import CAISConfig

    cfg = get_arch("mixtral-8x7b").smoke().scaled(
        num_layers=1, d_model=32, num_heads=4, num_kv_heads=4, head_dim=8,
        d_ff=48)
    cfg = cfg.scaled(moe=_dc.replace(cfg.moe, capacity_factor=8.0))
    mesh = sharding.make_mesh((1, 1), ("data", "model"))
    tpc = tp.TPContext(mesh=mesh, backend="cais",
                       cais=CAISConfig(num_chunks=1))
    params = tr.init_block(jax.random.key(60), "attn", cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(61), (2, 16, 32), jnp.float32)
    return tpc, x, params, cfg


def test_moe_sp_period_grad_matches_autodiff_single_device():
    """End-to-end on the tp=1 mesh: MoE period grads (incl. the aux-loss
    term) through sp_period's graph-built custom VJP match the
    graph_backward=False autodiff path."""
    import dataclasses as _dc

    tpc, x, params, cfg = _moe_mini_setup()

    def grads(tpc_):
        def f(x_, p_):
            out, aux = tp.sp_period(tpc_, x_, (p_,), cfg, ("attn",),
                                    norm_kind=cfg.norm)
            return jnp.sum(out * out) + aux
        return jax.grad(f, argnums=(0, 1))(x, params)

    g_vjp = grads(tpc)
    g_ref = grads(_dc.replace(tpc, graph_backward=False))
    for a, b in zip(jax.tree.leaves(g_vjp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_graph_backward_fallback_warns_once(monkeypatch):
    """When graph_backward=True and a period fails the adjoint-vocabulary
    gate, sp_period warns ONCE naming the offending op(s) (it used to fall
    back silently) and the fallback matches graph_backward=False exactly."""
    import dataclasses as _dc
    import warnings as _warnings

    import pytest

    tpc, x, params, cfg = _moe_mini_setup()
    monkeypatch.delitem(df.ADJOINTS, "a2a_ffn")
    monkeypatch.setattr(tp, "_GRAPH_BWD_WARNED", set())

    def loss(tpc_):
        def f(x_, p_):
            out, aux = tp.sp_period(tpc_, x_, (p_,), cfg, ("attn",),
                                    norm_kind=cfg.norm)
            return jnp.sum(out * out) + aux
        return f(x, params), jax.grad(f, argnums=(0, 1))(x, params)

    with pytest.warns(UserWarning, match="a2a_ffn"):
        l_fb, g_fb = loss(tpc)
    # second qualification failure with the same op set: no second warning
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", UserWarning)
        loss(tpc)
    l_ref, g_ref = loss(_dc.replace(tpc, graph_backward=False))
    np.testing.assert_allclose(np.asarray(l_fb), np.asarray(l_ref),
                               atol=1e-6)
    for a, b in zip(jax.tree.leaves(g_fb), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
