"""Tensor-parallel block execution (pjit-callable wrappers).

Every explicit-TP sub-layer dispatches through a
:class:`repro.core.backends.CollectiveBackend` — ``barrier`` (monolithic
NVLS-style collectives), ``cais`` (the paper's decomposed collective-fused
schedules), or any backend registered by the caller. ``auto`` is the
XLA-scheduled baseline: it reports ``explicit = False`` and the model path
skips ``shard_map`` entirely (plain jnp + sharding constraints).

The dense sub-layers are *IR-driven*: ``sp_ffn`` / ``sp_attention`` build a
:mod:`repro.core.dataflow` graph of primitive ops (LN, allgather, gemm_col,
gemm_row, reduce_scatter, local custom math), run the graph-level optimizer
(paper §III-C: compute-aware alignment, shared-gather multi fusion, deep
chain fusion, asymmetric pairing), and ``execute()`` the optimized graph
inside ``shard_map`` — so new fusion rules land in the transformer without
touching the sub-layers. The unit of execution is the sub-layer chain the
paper evaluates (L1–L4): [attention out-GEMM →RS] + LN + [AG→ FFN GEMMs].

The model path executes at *period* scope (:func:`sp_period`): every block
of a ``cfg.layer_pattern`` period concatenates into ONE graph run in ONE
``shard_map``, so the optimizer also sees the block→block seams —
cross-block RS→residual→LN→AG fusion (pass 2) and deterministic asymmetric
pairing (pass 3) fire inside ``stack_forward``, not just in tests.

A straight-line period is fully serialized after pass-2 fusion, so pass 3
has nothing to pair; ``num_microbatches`` (a :class:`TPContext` knob, or a
direct ``sp_period`` argument) splits the batch into that many independent
per-microbatch graph chains merged into the SAME graph
(``merge_graphs(share_weights=True)``) and re-concatenated inside the same
single ``shard_map`` — giving pass 3 the cross-chain ``gemm_rs`` /
``ag_gemm`` pairs it needs to emit ``overlap_asym`` inside the model path.
``"auto"`` sizes the split via :func:`repro.core.coordination.
plan_microbatches`. See ``docs/architecture.md`` for the full layer map.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from typing import TYPE_CHECKING, Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.core import coordination
from repro.core import dataflow as df
from repro.core.backends import CollectiveBackend, get_backend
from repro.core.primitives import CAISConfig

if TYPE_CHECKING:                                # pragma: no cover
    from repro.runtime import TPConfig

BATCH = sharding.BATCH_AXES
MODEL = sharding.MODEL_AXIS
# the data-parallel mesh axes a weight gradient must be psummed over (the
# batch is sharded across them; weights are replicated there)
_BATCH_AXES = BATCH if isinstance(BATCH, tuple) else (BATCH,)


@dataclass(frozen=True)
class TPContext:
    """Mesh + collective backend + chunking config for explicit TP.

    ``backend`` may be given as a registry name (``"barrier"``, ``"cais"``,
    …) or a :class:`CollectiveBackend` instance; it is resolved to an
    instance at construction. ``num_microbatches`` is the period-graph
    batch split (int, or ``"auto"`` to size it from the α-β model via
    :func:`repro.core.coordination.plan_microbatches`); 1 disables
    splitting. ``planner`` drives pass 3 of the graph optimizer:
    ``"greedy"`` (deterministic nearest-independent-first, the default) or
    ``"perfsim"`` (the :mod:`repro.plan` search: simulated-makespan argmin
    over pairings/chunks/microbatch splits, memoized in the plan cache).
    ``hw`` is the α-β target-hardware model the microbatch planner and the
    perfsim fabric read — injectable so tests can pin behaviour with a
    scaled-down fabric. ``graph_backward`` routes period training
    gradients — dense, MoE, and the replicated-activation decode/ragged
    layout — through the graph-built custom VJP (``docs/training.md``)
    instead of JAX autodiff of the executed forward."""

    mesh: Mesh
    backend: Union[str, CollectiveBackend] = "cais"
    cais: CAISConfig = CAISConfig()
    num_microbatches: Union[int, str] = 1
    planner: str = "greedy"
    hw: "coordination.HWSpec" = coordination.V5E
    graph_backward: bool = True

    def __post_init__(self):
        object.__setattr__(self, "backend", get_backend(self.backend))
        # thread the target-hardware model into the cais chunk planner so
        # the backend can plan per-tier chunk counts (inter-node legs plan
        # against hw.inter_tier()) without a second plumbing path
        if self.cais.hw is None:
            object.__setattr__(
                self, "cais", dataclasses.replace(self.cais, hw=self.hw))

    @classmethod
    def from_config(cls, tp: "TPConfig", mesh: Mesh,
                    hw: "coordination.HWSpec" = coordination.V5E
                    ) -> "TPContext":
        """THE construction path from the runtime-level
        :class:`repro.runtime.TPConfig` to an execution context. Every model
        entry point (``models/transformer``, ``serve/engine``, launchers)
        routes through here so a ``Runtime.tp`` knob can never silently
        diverge from what the mesh actually executes."""
        return cls(mesh=mesh, backend=tp.mode,
                   cais=CAISConfig(num_chunks=tp.chunks,
                                   bidirectional=tp.bidirectional),
                   num_microbatches=tp.microbatches, planner=tp.planner,
                   hw=hw, graph_backward=tp.graph_backward)

    @property
    def mode(self) -> str:
        return self.backend.name

    @property
    def tp(self) -> int:
        """Total TP degree (flat axis size, or tp_in·tp_out on a 2D mesh)."""
        return sharding.tp_size(self.mesh)

    @property
    def tp_axes(self):
        """The TP axis entry for specs / collectives: ``"model"`` on a flat
        mesh, the composite ``("tp_in", "tp_out")`` tuple on a 2D one."""
        return sharding.tp_axes(self.mesh)

    @property
    def is_2d(self) -> bool:
        return isinstance(self.tp_axes, tuple)

    @property
    def route_axis(self):
        """The axis the MoE expert all-to-all crosses: the slow ``tp_out``
        ring on a 2D mesh (grouped-EP — experts replicate across ``tp_in``),
        the full model axis on a flat one."""
        ax = self.tp_axes
        return ax[-1] if isinstance(ax, tuple) else ax

    @property
    def route_ring(self) -> int:
        """Ring size of :attr:`route_axis` (the expert-sharding degree)."""
        return sharding.axis_size(self.mesh, self.route_axis)

    @property
    def topology(self):
        """(n_inner, n_outer) ring sizes — (tp, 1) on a flat mesh."""
        ax = self.tp_axes
        if isinstance(ax, tuple):
            return (sharding.axis_size(self.mesh, ax[0]),
                    sharding.axis_size(self.mesh, ax[-1]))
        return (self.tp, 1)


def _specs(mesh, *entries):
    return sharding._filter_spec(mesh, P(*entries))


def _smap(tpc: TPContext, fn, in_specs, out_specs):
    return sharding.shard_map(
        fn, mesh=tpc.mesh,
        in_specs=tuple(_specs(tpc.mesh, *s) for s in in_specs),
        out_specs=(tuple(_specs(tpc.mesh, *s) for s in out_specs)
                   if isinstance(out_specs, list)
                   else _specs(tpc.mesh, *out_specs)),
        check_vma=False)


@dataclass(frozen=True)
class SPOptions:
    """Shared keyword-only options for the ``sp_*`` entry points
    (``sp_ffn`` / ``sp_attention`` / ``sp_block`` / ``sp_period``), so new
    execution knobs land in one place instead of being re-threaded through
    every signature. Pass as ``opts=SPOptions(...)``; the individual fields
    are also still accepted as direct keywords (folded into the options
    object) so existing call sites keep working.

    ``prefix_len`` marks leading prefix-LM (bidirectional) positions;
    ``window`` is the SWA window for :func:`sp_attention` (period entry
    points take it from the block kind); ``seq_sharded=False`` selects the
    decode/ragged replicated-activation allreduce schedule;
    ``num_microbatches`` overrides the :class:`TPContext` knob for one call."""

    prefix_len: int = 0
    norm_kind: str = "rmsnorm"
    seq_sharded: bool = True
    num_microbatches: Union[int, str, None] = None
    window: int = 0


def _sp_opts(opts: Optional[SPOptions], legacy: dict) -> SPOptions:
    """Fold direct-keyword options into an :class:`SPOptions`."""
    opts = opts if opts is not None else SPOptions()
    if legacy:
        bad = sorted(set(legacy) - set(SPOptions.__dataclass_fields__))
        if bad:
            raise TypeError(f"unknown sp_* option {bad[0]!r}")
        opts = dataclasses.replace(opts, **legacy)
    return opts


# ---------------------------------------------------------------------------
# Sub-layer dataflow graphs (lowered via dataflow.optimize + execute)
# ---------------------------------------------------------------------------


def _ffn_chain_nodes(src: str, out: str, has_gate: bool, act: str,
                     tag: str = "", p: str = "",
                     seq_sharded: bool = True) -> list:
    """AG → GEMM(up[, gate]) → act[(·)] → GEMM(down) → RS nodes from value
    ``src`` to value ``out`` (weight keys w_up/w_gate/w_down); ``tag``
    uniquifies node names when the chain is embedded in a larger graph and
    ``p`` namespaces node names AND weight keys (period graphs, one prefix
    per block). With ``seq_sharded=False`` (decode-style TP: the activation
    is replicated, not sequence-sharded) the gather is skipped and the chain
    ends in an allreduce instead of a reduce-scatter."""
    from repro.models.layers import activation

    ag, up, gate, h, down = (f"{p}agx{tag}", f"{p}up{tag}", f"{p}gate{tag}",
                             f"{p}h{tag}", f"{p}down{tag}")
    nodes = []
    if seq_sharded:
        nodes.append(df.Node(ag, "allgather", (src,)))
        gin = ag
    else:
        gin = src
    nodes.append(df.Node(up, "gemm_col", (gin,), (p + "w_up",)))
    if has_gate:
        nodes.append(df.Node(gate, "gemm_col", (gin,), (p + "w_gate",)))
        nodes.append(df.Node(h, "custom", (up, gate),
                             fn=lambda u, g: activation(act, g) * u))
    else:
        nodes.append(df.Node(h, "custom", (up,),
                             fn=lambda u: activation(act, u)))
    nodes += [
        df.Node(down, "gemm_row", (h,), (p + "w_down",)),
        df.Node(out, "reduce_scatter" if seq_sharded else "allreduce",
                (down,)),
    ]
    return nodes


def ffn_sublayer_graph(has_gate: bool, act: str) -> df.Graph:
    """LN → AG → GEMM(up[, gate]) → act[(·)] → GEMM(down) → RS as IR nodes.
    ``optimize()`` turns the collectives into the backend's fused schedules
    (ag_gemm / ag_gemm_multi / gemm_rs)."""
    nodes = [
        df.Node("x", "input"),
        df.Node("ln", "layernorm", ("x",), ("scale",)),
    ] + _ffn_chain_nodes("ln", "out", has_gate, act)
    return df.Graph(nodes, outputs=("out",))


def attention_sublayer_graph(core_fn: Callable) -> df.Graph:
    """LN → AG → GEMM(q|k|v) → attention core → GEMM(out) → RS as IR nodes.
    ``core_fn(q, k, v)`` is the local attention math (rope, KV slicing,
    flash core, head reshape) — a ``custom`` node the optimizer schedules
    collectives around."""
    nodes = [
        df.Node("x", "input"),
        df.Node("ln", "layernorm", ("x",), ("scale",)),
        df.Node("agx", "allgather", ("ln",)),
        df.Node("q", "gemm_col", ("agx",), ("wq",)),
        df.Node("k", "gemm_col", ("agx",), ("wk",)),
        df.Node("v", "gemm_col", ("agx",), ("wv",)),
        df.Node("o", "custom", ("q", "k", "v"), fn=core_fn),
        df.Node("proj", "gemm_row", ("o",), ("wo",)),
        df.Node("out", "reduce_scatter", ("proj",)),
    ]
    return df.Graph(nodes, outputs=("out",))


# ---------------------------------------------------------------------------
# Block graph fragments: attention residual → FFN/MoE residual as namespaced
# node lists that chain into whole-block and whole-PERIOD graphs, so pass 2
# fuses the rs→ln→ag seams between sub-layers AND between blocks, and pass 3
# can co-schedule collectives across independent chains (microbatches).
# ---------------------------------------------------------------------------


def _attention_block_nodes(core_fn: Callable, p: str = "", src: str = "x",
                           seq_sharded: bool = True) -> list:
    """src → LN1 → [AG →] QKV → core → out-GEMM → RS|AR → +src residual
    (value ``{p}r1``). ``p`` namespaces node names and weight keys; with
    ``seq_sharded=False`` the gather is skipped (replicated activation) and
    the out-projection reduces with an allreduce."""
    nodes = [df.Node(f"{p}ln1", "layernorm", (src,), (f"{p}scale1",))]
    if seq_sharded:
        nodes.append(df.Node(f"{p}agx1", "allgather", (f"{p}ln1",)))
        gin = f"{p}agx1"
    else:
        gin = f"{p}ln1"
    nodes += [
        df.Node(f"{p}q", "gemm_col", (gin,), (f"{p}wq",)),
        df.Node(f"{p}k", "gemm_col", (gin,), (f"{p}wk",)),
        df.Node(f"{p}v", "gemm_col", (gin,), (f"{p}wv",)),
        df.Node(f"{p}o", "custom", (f"{p}q", f"{p}k", f"{p}v"), fn=core_fn),
        df.Node(f"{p}proj", "gemm_row", (f"{p}o",), (f"{p}wo",)),
        df.Node(f"{p}rs1", "reduce_scatter" if seq_sharded else "allreduce",
                (f"{p}proj",)),
        df.Node(f"{p}r1", "residual", (f"{p}rs1", src)),
    ]
    return nodes


def _dense_block_nodes(core_fn: Callable, has_gate: bool, act: str,
                       p: str = "", src: str = "x",
                       seq_sharded: bool = True):
    """One dense block as a graph fragment: returns (nodes, out_value)."""
    nodes = _attention_block_nodes(core_fn, p, src, seq_sharded) + [
        df.Node(f"{p}ln2", "layernorm", (f"{p}r1",), (f"{p}scale2",)),
    ] + _ffn_chain_nodes(f"{p}ln2", f"{p}rs2", has_gate, act, tag="2", p=p,
                         seq_sharded=seq_sharded) + [
        df.Node(f"{p}r2", "residual", (f"{p}rs2", f"{p}r1")),
    ]
    return nodes, f"{p}r2"


def _moe_block_nodes(core_fn: Callable, route_fn: Callable,
                     expert_fn: Callable, unroute_fn: Callable,
                     expert_weights: tuple,
                     dense_fn: Optional[Callable] = None,
                     dense_weights: tuple = (), p: str = "",
                     src: str = "x"):
    """One MoE block as a graph fragment: returns (nodes, out_value,
    aux_value). ``expert_weights``/``dense_weights`` are the (already
    namespaced) weight keys of the expert FFN / dense-residual MLP."""
    nodes = _attention_block_nodes(core_fn, p, src) + [
        df.Node(f"{p}ln2", "layernorm", (f"{p}r1",), (f"{p}scale2",)),
        df.Node(f"{p}moe_route", "route", (f"{p}ln2",), (f"{p}router",),
                outputs=(f"{p}send", f"{p}combine", f"{p}aux"), fn=route_fn),
        df.Node(f"{p}eout", "a2a_ffn", (f"{p}send",), expert_weights,
                fn=expert_fn),
        df.Node(f"{p}y", "unroute", (f"{p}eout", f"{p}combine", f"{p}ln2"),
                fn=unroute_fn),
    ]
    top = f"{p}y"
    if dense_fn is not None:
        nodes.append(df.Node(f"{p}dmlp", "custom", (f"{p}ln2",),
                             dense_weights, fn=dense_fn))
        nodes.append(df.Node(f"{p}ymoe", "add", (top, f"{p}dmlp")))
        top = f"{p}ymoe"
    nodes.append(df.Node(f"{p}r2", "residual", (top, f"{p}r1")))
    return nodes, f"{p}r2", f"{p}aux"


def dense_block_graph(core_fn: Callable, has_gate: bool, act: str) -> df.Graph:
    """One Graph for a whole dense transformer block. After ``optimize()``
    the attention-out RS, the residual add, LN2, and the FFN input gather
    collapse into one ``fused_rs_ln_ag[_multi]`` pipeline (pass 2) — the
    cross-sub-layer seam a per-sub-layer graph can never see."""
    nodes, out = _dense_block_nodes(core_fn, has_gate, act)
    return df.Graph([df.Node("x", "input")] + nodes, outputs=(out,))


def dense_period_graph(core_fns: Sequence[Callable], has_gate: bool,
                       act: str) -> df.Graph:
    """One Graph for a PERIOD of dense blocks (one core_fn per block),
    chained through per-block ``b{i}.`` namespaces. With ≥2 blocks the
    optimizer sees the block→block seam: block k's FFN-out RS → residual →
    block k+1's LN1 → QKV shared gather fuses into one cross-block
    ``fused_rs_ln_ag_multi`` (pass 2)."""
    nodes = [df.Node("x", "input")]
    src = "x"
    for i, core_fn in enumerate(core_fns):
        ns, src = _dense_block_nodes(core_fn, has_gate, act, p=f"b{i}.",
                                     src=src)
        nodes += ns
    return df.Graph(nodes, outputs=(src,))


def moe_block_graph(core_fn: Callable, route_fn: Callable,
                    expert_fn: Callable, unroute_fn: Callable,
                    expert_weights: tuple, has_gate: bool,
                    dense_fn: Optional[Callable] = None,
                    dense_weights: tuple = ()) -> df.Graph:
    """One Graph for a whole MoE transformer block: the expert path runs as
    ``route → a2a_ffn → unroute`` IR nodes, with ``a2a_ffn`` dispatched
    through ``CollectiveBackend.a2a_expert_ffn``. ``dense_fn`` adds the
    Arctic-style parallel dense-residual MLP as a ``custom`` node. Pass 2
    fuses the attention-out RS → residual → LN2 → router seam into
    ``fused_rs_ln`` (the trailing collective is the expert all-to-all)."""
    nodes, out, aux = _moe_block_nodes(core_fn, route_fn, expert_fn,
                                       unroute_fn, expert_weights,
                                       dense_fn, dense_weights)
    return df.Graph([df.Node("x", "input")] + nodes, outputs=(out, aux))


# ---------------------------------------------------------------------------
# FFN sub-layer: LN -> AG-GEMM(up[,gate]) -> act -> GEMM-RS(down)
# ---------------------------------------------------------------------------


def sp_ffn(tpc: TPContext, x, norm_scale, w_up, w_gate, w_down,
           act: str, *, opts: Optional[SPOptions] = None, **kw):
    """x: (B, S, d) logically sequence-sharded. Returns FFN(LN(x)) with the
    residual handled by the caller. ``w_gate`` may be None. Options (e.g.
    ``norm_kind``) via ``opts`` / :class:`SPOptions` keywords."""
    o = _sp_opts(opts, kw)
    norm_kind = o.norm_kind
    has_gate = w_gate is not None
    graph = df.optimize(ffn_sublayer_graph(has_gate, act))
    wnames = ("scale", "w_up") + (("w_gate",) if has_gate else ()) + \
        ("w_down",)

    M = tpc.tp_axes

    def local(x, *ws):
        return df.execute(graph, {"x": x}, dict(zip(wnames, ws)),
                          axis=M, cais=tpc.cais, norm=norm_kind,
                          backend=tpc.backend)[0]

    in_specs = [(BATCH, M, None),                # x sequence-sharded
                (None,),                         # norm scale replicated
                (None, M)]                       # up col-sharded
    if has_gate:
        in_specs.append((None, M))               # gate col-sharded
    in_specs.append((M, None))                   # down row-sharded
    args = (x, norm_scale, w_up) + ((w_gate,) if has_gate else ()) + \
        (w_down,)
    return _smap(tpc, local, in_specs, (BATCH, M, None))(*args)


# ---------------------------------------------------------------------------
# Attention sub-layer: LN -> AG-GEMM(QKV) -> attn core -> GEMM-RS(out)
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_applies(seq_len: int, dh: int, heads: int, kv_heads: int,
                   window: int, prefix_len: int) -> bool:
    """Whether a local attention core runs the Pallas causal flash kernel
    (``kernels.ops.causal_flash_attention``): plain causal MHA on the TPU
    with a sequence the kernel's block divides and a lane-aligned head
    size. Everything else keeps the XLA core."""
    from repro.kernels import ops

    return (window == 0 and prefix_len == 0 and heads == kv_heads
            and dh % ops.LANE == 0 and ops.flash_block(seq_len) is not None
            and _on_tpu())


def _attention_core_fn(cfg, tp: int, window: int = 0, prefix_len: int = 0,
                       axis=MODEL) -> Callable:
    """The local attention math (rope, KV head slicing, attention core, head
    reshape) as a closure for a ``custom`` IR node — shared by
    :func:`sp_attention` and :func:`sp_block`. ``axis`` is the TP axis entry
    (a name, or the composite 2D tuple — the replicated-KV slice uses the
    flattened shard index, which matches contiguous head sharding).

    The core is the Pallas flash kernel where :func:`_flash_applies` (under
    the scope ``attn.flash``), else ``attention_core``'s chunked XLA scan
    (under ``attn.xla``); both compute the same function."""
    from repro.kernels import ops
    from repro.models.attention import attention_core
    from repro.models.layers import apply_rope

    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    kv_sharded = Hkv % tp == 0

    def core(q, k, v):
        B_, S = q.shape[0], q.shape[1]
        H_loc = max(H // tp, 1)
        Hkv_loc = max(Hkv // tp, 1) if kv_sharded else Hkv
        pos = jnp.broadcast_to(jnp.arange(S), (B_, S))
        # rope in float32 either way; q keeps it until the core rounds it
        q32 = apply_rope(q.reshape(B_, S, H_loc, dh).astype(jnp.float32),
                         pos, cfg.rope_theta)
        k = apply_rope(k.reshape(B_, S, Hkv_loc, dh), pos, cfg.rope_theta)
        v = v.reshape(B_, S, Hkv_loc, dh)
        if not kv_sharded:
            # replicated KV: slice the kv heads this device's q heads use
            # (contiguous because head sharding is contiguous)
            g = H // Hkv                    # q heads per kv head
            need = max(H_loc // g, 1)
            start = (sharding.shard_map_axis_index(axis) * H_loc) // g
            k = jax.lax.dynamic_slice_in_dim(k, start, need, axis=2)
            v = jax.lax.dynamic_slice_in_dim(v, start, need, axis=2)
        if _flash_applies(S, dh, H_loc, k.shape[2], window, prefix_len):
            with jax.named_scope("attn.flash"):
                o = ops.causal_flash_attention(q32, k, v)
        else:
            with jax.named_scope("attn.xla"):
                o = attention_core(q32.astype(q.dtype), k, v,
                                   q_positions=pos, kv_positions=pos,
                                   causal=True, window=window,
                                   prefix_len=prefix_len)
        return o.reshape(B_, S, H_loc * dh)

    return core


def sp_attention(tpc: TPContext, x, norm_scale, wq, wk, wv, wo, cfg, *,
                 opts: Optional[SPOptions] = None, **kw):
    """Full Megatron-SP attention block over the collective backend.
    x: (B, S, d) sequence-sharded; Q heads shard over `model`. When
    num_kv_heads < tp (GQA/MQA), K/V weights replicate and every device
    computes the full K/V from the same gathered activation chunks — the
    standard Megatron KV-replication, and the gather is still shared with
    the Q projection (one ring circulation feeds all three). Options
    (``window``, ``prefix_len``, ``norm_kind``) via ``opts`` /
    :class:`SPOptions` keywords."""
    o = _sp_opts(opts, kw)
    norm_kind = o.norm_kind
    tp = tpc.tp
    M = tpc.tp_axes
    kv_sharded = cfg.num_kv_heads % tp == 0
    core = _attention_core_fn(cfg, tp, window=o.window,
                              prefix_len=o.prefix_len, axis=M)

    graph = df.optimize(attention_sublayer_graph(core))

    def local(x, norm_scale, wq, wk, wv, wo):
        return df.execute(graph, {"x": x},
                          {"scale": norm_scale, "wq": wq, "wk": wk,
                           "wv": wv, "wo": wo},
                          axis=M, cais=tpc.cais, norm=norm_kind,
                          backend=tpc.backend)[0]

    kv_spec = (None, M) if kv_sharded else (None, None)
    return _smap(
        tpc, local,
        in_specs=[(BATCH, M, None), (None,),
                  (None, M), kv_spec, kv_spec,
                  (M, None)],
        out_specs=(BATCH, M, None))(x, norm_scale, wq, wk, wv, wo)


# ---------------------------------------------------------------------------
# MoE FFN sub-layer over EP: backend-dispatched expert all-to-all
# ---------------------------------------------------------------------------


def sp_moe_ffn(tpc: TPContext, x, norm_scale, params, cfg,
               norm_kind: str = "rmsnorm"):
    """MoE FFN with the backend's expert-a2a pipeline (beyond-paper
    extension, EXPERIMENTS.md §Perf cell 2): each device routes its sequence
    shard's tokens to expert owners; the ``cais`` backend overlaps the
    interleaved ±direction dispatch/combine permutes with the expert GEMMs.

    Owner mapping: rank j of the ROUTE ring owns experts
    [j·E_loc, (j+1)·E_loc) when E ≥ ring (E % ring == 0); when E < ring
    (ring % E == 0) expert e lives on rank e·(ring/E) and the others idle
    through the FFN (their buffers are zero-capacity padding). On a flat
    mesh the route ring is the whole model axis; on a hierarchical 2D mesh
    it is the slow ``tp_out`` axis only — grouped EP: expert weights shard
    over ``tp_out`` and replicate across ``tp_in``, so the all-to-all never
    crosses the fast intra-node links redundantly (docs/topology.md). This
    is what makes E < tp configurations first-class: E=4 on an 8-way 2×4
    mesh is plain E % tp_out == 0 expert sharding. x: (B, S, d)
    sequence-sharded. Returns FFN(LN(x)) (residual handled by the caller)
    and the load-balancing aux loss.

    The routing/expert/combine math is shared with the whole-block IR path
    (:func:`sp_block`) via the :func:`_moe_graph_fns` closures."""
    from repro.models.layers import apply_norm

    m = cfg.moe
    E = m.num_experts
    ring = tpc.route_ring
    M = tpc.tp_axes
    cais = tpc.cais
    has_gate = "w_gate" in params
    route_fn, expert_fn, unroute_fn = _moe_graph_fns(
        cfg, ring, has_gate, route_axis=tpc.route_axis)

    def local(x, ns, router, wu, wg, wd):
        xn = apply_norm(norm_kind, {"scale": ns}, x)
        send, combine, aux = route_fn(xn, router)
        ws = (wu, wg, wd) if has_gate else (wu, wd)
        ret = tpc.backend.a2a_expert_ffn(
            send, lambda chunk: expert_fn(chunk, *ws), M, cais)
        out = unroute_fn(ret, combine, xn)
        if m.dense_residual_d_ff:
            from repro.models.ffn import mlp_forward
            out = out + mlp_forward(params["dense"], xn, cfg.act)
        # aux leaves sharded over (batch, model) — the per-shard statistics
        # genuinely differ per data shard (same convention as sp_period)
        return out, aux[None]

    dtype = x.dtype
    wu = params["w_up"].astype(dtype)
    wg = params["w_gate"].astype(dtype) if has_gate else \
        jnp.zeros_like(params["w_up"], dtype)
    wd = params["w_down"].astype(dtype)
    e_spec = (tpc.route_axis, None, None) if E % ring == 0 \
        else (None, None, None)
    out, aux = _smap(
        tpc, local,
        in_specs=[(BATCH, M, None), (None,), (None, None),
                  e_spec, e_spec, e_spec],
        out_specs=[(BATCH, M, None), (BATCH, M)])(
            x, norm_scale, params["router"], wu, wg, wd)
    return out, jnp.mean(aux)


# ---------------------------------------------------------------------------
# Whole-block execution: ONE dataflow graph per transformer block
# ---------------------------------------------------------------------------


def _moe_graph_fns(cfg, ring: int, has_gate: bool, route_axis=MODEL):
    """Closures for the MoE expert path (route / a2a expert compute /
    unroute) — the single home of this math, used both as IR node ``fn``s
    by :func:`sp_block`'s graph and composed directly by
    :func:`sp_moe_ffn`. ``ring`` is the size of the all-to-all ring and
    ``route_axis`` its mesh axis name: the full model axis on a flat mesh,
    the slow ``tp_out`` axis on a hierarchical 2D mesh (grouped EP). Owner
    mapping as documented on ``sp_moe_ffn``: ring rank j owns experts
    [j·E_loc, (j+1)·E_loc) when E ≥ ring; when E < ring expert e lives on
    rank e·(ring/E) (replicated weights sliced per owner, zero-capacity
    padding elsewhere)."""
    from repro.models.ffn import _top2_dispatch
    from repro.models.layers import activation

    m = cfg.moe
    E = m.num_experts
    E_loc = max(E // ring, 1)

    def route_fn(xn, router):
        B, S_loc, d = xn.shape
        t = xn.reshape(B * S_loc, d)
        T = t.shape[0]
        logits = t.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, -1)
        cap = max(1, int(T * m.top_k / E * m.capacity_factor))
        dispatch, combine, aux = _top2_dispatch(probs[None], cap)
        dispatch, combine = dispatch[0], combine[0]     # (T, E, cap)
        # send[j]: (E_loc·cap, d) tokens for the experts device j owns
        de = jnp.einsum("tec,td->ecd", dispatch.astype(t.dtype), t)
        if E >= ring:
            send = de.reshape(ring, E_loc * cap, d)
        else:
            # owner(e) = e·(ring/E); other ranks get zero-capacity padding
            stride = ring // E
            send = jnp.zeros((ring, cap, d), t.dtype)
            send = send.at[::stride].set(de)
        return send, combine, aux.astype(jnp.float32)[None]

    def expert_fn(chunk, wu, *rest):
        # chunk: (E_loc·cap, d) → per-local-expert gated FFN
        wg = rest[0] if has_gate else None
        wd = rest[-1]
        if E < ring:
            # replicated weights: slice this owner's single expert
            eidx = jax.lax.axis_index(route_axis) // (ring // E)
            wu = jax.lax.dynamic_index_in_dim(wu, eidx, 0, keepdims=True)
            wd = jax.lax.dynamic_index_in_dim(wd, eidx, 0, keepdims=True)
            if has_gate:
                wg = jax.lax.dynamic_index_in_dim(wg, eidx, 0, keepdims=True)
        c = chunk.reshape(E_loc, -1, chunk.shape[-1])
        h = jnp.einsum("ecd,edf->ecf", c, wu)
        if has_gate:
            g = jnp.einsum("ecd,edf->ecf", c, wg)
            h = activation(cfg.act, g) * h
        else:
            h = activation(cfg.act, h)
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        return out.reshape(chunk.shape)

    def unroute_fn(ret, combine, xn):
        B, S_loc, d = xn.shape
        cap = combine.shape[-1]
        if E >= ring:
            eout = ret.reshape(E, cap, d)
        else:
            eout = ret[::ring // E]
        y = jnp.einsum("tec,ecd->td", combine.astype(ret.dtype), eout)
        return y.reshape(B, S_loc, d)

    return route_fn, expert_fn, unroute_fn


def _block_graph_fragment(tpc: TPContext, params, cfg, kind: str, idx: int,
                          src: str, prefix_len: int = 0,
                          dtype=jnp.float32, seq_sharded: bool = True):
    """One transformer block as a period-graph fragment: nodes chained from
    value ``src``, every node name and weight key namespaced ``b{idx}.``.
    Returns (nodes, out_value, aux_value_or_None, weights, specs) —
    ``weights`` maps the namespaced keys to local param arrays and ``specs``
    to their shard_map PartitionSpec entries."""
    p = f"b{idx}."
    tp = tpc.tp
    M = tpc.tp_axes
    m = params["mixer"]
    kv_sharded = cfg.num_kv_heads % tp == 0
    window = cfg.window if kind == "swa" else 0
    core = _attention_core_fn(cfg, tp, window=window, prefix_len=prefix_len,
                              axis=M)

    kv_spec = (None, M) if kv_sharded else (None, None)
    weights = {
        p + "scale1": params["norm1"]["scale"].astype(dtype),
        p + "wq": m["wq"].astype(dtype), p + "wk": m["wk"].astype(dtype),
        p + "wv": m["wv"].astype(dtype), p + "wo": m["wo"].astype(dtype),
        p + "scale2": params["norm2"]["scale"].astype(dtype),
    }
    specs = {
        p + "scale1": (None,), p + "wq": (None, M), p + "wk": kv_spec,
        p + "wv": kv_spec, p + "wo": (M, None), p + "scale2": (None,),
    }

    f = params["ffn"]
    has_gate = "w_gate" in f
    moe = cfg.moe is not None
    if moe:
        ring = tpc.route_ring
        assert seq_sharded, \
            "MoE blocks run only on the sequence-sharded period path"
        assert cfg.moe.num_experts % ring == 0, \
            "sp_block MoE path requires E % route_ring == 0 " \
            "(see tp_applicable)"
        route_fn, expert_fn, unroute_fn = _moe_graph_fns(
            cfg, ring, has_gate, route_axis=tpc.route_axis)
        weights[p + "router"] = f["router"]             # stays float32
        specs[p + "router"] = (None, None)
        e_keys = tuple(p + kk for kk in ("w_up",)
                       + (("w_gate",) if has_gate else ()) + ("w_down",))
        for kkey in e_keys:
            weights[kkey] = f[kkey[len(p):]].astype(dtype)
            # grouped EP on a 2D mesh: experts shard over tp_out only and
            # replicate across tp_in (gradients psum over tp_in in
            # local_bwd's missing-axes pass)
            specs[kkey] = (tpc.route_axis, None, None)
        dense_fn, d_keys = None, ()
        if cfg.moe.dense_residual_d_ff:
            dm = f["dense"]
            dense_gate = "w_gate" in dm
            d_keys = tuple(p + kk for kk in ("d_up",)
                           + (("d_gate",) if dense_gate else ())
                           + ("d_down",))
            weights[p + "d_up"] = dm["w_up"].astype(dtype)
            if dense_gate:
                weights[p + "d_gate"] = dm["w_gate"].astype(dtype)
            weights[p + "d_down"] = dm["w_down"].astype(dtype)
            for kkey in d_keys:
                specs[kkey] = (None, None)
            from repro.models.layers import activation

            def dense_fn(xn, du, *drest):
                dg = drest[0] if dense_gate else None
                dd = drest[-1]
                h = xn @ du
                if dense_gate:
                    h = activation(cfg.act, xn @ dg) * h
                else:
                    h = activation(cfg.act, h)
                return h @ dd

        nodes, out, aux = _moe_block_nodes(core, route_fn, expert_fn,
                                           unroute_fn, e_keys, dense_fn,
                                           d_keys, p=p, src=src)
    else:
        nodes, out = _dense_block_nodes(core, has_gate, cfg.act, p=p,
                                        src=src, seq_sharded=seq_sharded)
        aux = None
        weights[p + "w_up"] = f["w_up"].astype(dtype)
        specs[p + "w_up"] = (None, M)
        if has_gate:
            weights[p + "w_gate"] = f["w_gate"].astype(dtype)
            specs[p + "w_gate"] = (None, M)
        weights[p + "w_down"] = f["w_down"].astype(dtype)
        specs[p + "w_down"] = (M, None)
    return nodes, out, aux, weights, specs


def _period_graph(tpc: TPContext, params_seq, cfg, kinds: Sequence[str],
                  prefix_len: int = 0, dtype=jnp.float32,
                  seq_sharded: bool = True):
    """The single-chain period graph :func:`sp_period` executes: every block
    in ``kinds`` chained through per-block ``b{i}.`` namespaces from input
    ``x``. Returns (graph, weights dict, specs dict, aux value names)."""
    nodes = [df.Node("x", "input")]
    weights, specs, aux_vals = {}, {}, []
    src = "x"
    for i, (params, kind) in enumerate(zip(params_seq, kinds)):
        ns, src, aux, w, s = _block_graph_fragment(
            tpc, params, cfg, kind, i, src, prefix_len=prefix_len,
            dtype=dtype, seq_sharded=seq_sharded)
        clash = sorted(set(w) & set(weights))
        if clash:
            raise df.GraphError(
                f"period graph weight key collision on {clash[0]!r} "
                f"(block {i})")
        nodes += ns
        weights.update(w)
        specs.update(s)
        if aux is not None:
            aux_vals.append(aux)
    graph = df.Graph(nodes, outputs=(src,) + tuple(aux_vals))
    return graph, weights, specs, tuple(aux_vals)


def microbatch_period_graph(base: df.Graph, num_microbatches: int) -> df.Graph:
    """``num_microbatches`` independent copies of a single-chain period graph
    merged into ONE graph (``mb{i}.``-prefixed values, SHARED weight keys) —
    the in-model microbatch split. After ``optimize()`` pass 3 cross-pairs
    collectives from different chains (``overlap_asym``), which a
    straight-line period can never expose. ``num_microbatches=1`` returns
    ``base`` unchanged (the unsplit path, bit-identical)."""
    if num_microbatches <= 1:
        return base
    return df.merge_graphs([base] * num_microbatches, share_weights=True)


def resolve_microbatches(tpc: TPContext, x,
                         requested: Union[int, str, None] = None,
                         moe: bool = False) -> int:
    """The effective period-graph batch split for activation ``x``
    ((B, S, d), global). ``requested=None`` defers to
    ``tpc.num_microbatches``; ``"auto"`` asks
    :func:`repro.core.coordination.plan_microbatches` with the per-device
    batch and the full gathered-activation payload. The result is clamped
    to the largest value that divides the per-device batch (1 = unsplit).

    ``moe=True`` (the period contains MoE blocks) disables ``"auto"``
    splitting: the MoE load-balance aux loss is a per-(micro)batch
    statistic that is NOT linear over sub-batches, so splitting changes
    the training objective's aux term — that trade-off must be an explicit
    integer opt-in, never a silent default."""
    req = tpc.num_microbatches if requested is None else requested
    b_loc = max(int(x.shape[0]) // max(sharding.dp_size(tpc.mesh), 1), 1)
    if req == "auto":
        if moe:
            return 1
        payload = b_loc * int(x.shape[1]) * int(x.shape[2]) * \
            np.dtype(x.dtype).itemsize
        n_in, n_out = tpc.topology
        if n_out > 1:
            # 2D mesh: the slow inter-node tier dominates the collective
            # time the split amortizes — plan against the tp_out ring with
            # the inter-tier α-β model and the per-node payload (the outer
            # exchange moves 1/tp_in of the gathered activation per rank)
            mb = coordination.plan_microbatches(
                b_loc, float(payload) / max(n_in, 1), n_out,
                bidirectional=tpc.cais.bidirectional,
                hw=tpc.hw.inter_tier())
        else:
            mb = coordination.plan_microbatches(b_loc, float(payload),
                                                tpc.tp,
                                                bidirectional=
                                                tpc.cais.bidirectional,
                                                hw=tpc.hw)
    else:
        mb = int(req)
    mb = max(1, min(mb, b_loc))
    while b_loc % mb:
        mb -= 1
    return mb


def _core_comp_hints(cfg, kinds: Sequence[str], batch: int, seq: int
                     ) -> Dict[str, float]:
    """Planner ``comp_hints`` for a single-chain period graph: the attention
    cores (``b{i}.o`` custom nodes) and the routed expert FFNs (``b{i}.eout``
    a2a_ffn nodes) are the op classes whose cost the lowering cannot read
    off GEMM weight shapes, so their FLOPs come from
    :mod:`repro.models.counting`. Keys are base-graph node names
    (per-replica ``batch``, like the planner's value shapes);
    :func:`repro.plan.search.microbatch_comp_hints` re-prefixes and
    re-scales them per microbatch chain, and :func:`_bwd_planner` doubles
    each hint for the matching ``adj.`` node."""
    from repro.models.counting import attention_core_flops, expert_ffn_flops

    flops = attention_core_flops(cfg, batch, seq)
    hints = {f"b{i}.o": flops for i in range(len(kinds))}
    if cfg.moe is not None:
        ef = expert_ffn_flops(cfg, batch, seq)
        hints.update({f"b{i}.eout": ef
                      for i, k in enumerate(kinds) if k == "moe"})
    return hints


def _plan_period(tpc: TPContext, base: df.Graph, weights, x,
                 requested: Union[int, str, None], moe: bool,
                 comp_hints: Optional[Dict[str, float]] = None):
    """The (num_microbatches, pass-3 planner) decision for one period graph
    under ``tpc.planner``.

    ``"greedy"`` keeps the α-β heuristic split (:func:`resolve_microbatches`)
    and the nearest-first pairing (planner None). ``"perfsim"`` hands the
    whole decision to :func:`repro.plan.search.period_planner`: microbatch
    candidates (the α-β path's power-of-two menu; an explicit integer
    request stays fixed — the planner then only decides pairing/chunking;
    MoE periods never auto-split, same contract as the greedy path) are
    scored by simulated makespan together with pass-3 pairings and chunk
    counts, memoized in the process-wide plan cache."""
    if tpc.planner != "perfsim":
        return resolve_microbatches(tpc, x, requested, moe), None
    from repro import plan as plan_mod

    req = tpc.num_microbatches if requested is None else requested
    b_loc = max(int(x.shape[0]) // max(sharding.dp_size(tpc.mesh), 1), 1)
    if req == "auto":
        cands = (1,) if moe else tuple(
            m for m in (1, 2, 4) if m <= b_loc and b_loc % m == 0)
    else:
        cands = (resolve_microbatches(tpc, x, requested, moe),)
    x_shape = (b_loc, int(x.shape[1]), int(x.shape[2]))
    plan, pairer = plan_mod.period_planner(
        base, x_shape=x_shape,
        weight_shapes={k: tuple(v.shape) for k, v in weights.items()},
        dtype_bytes=np.dtype(x.dtype).itemsize, tp=tpc.tp,
        backend=tpc.mode, mb_candidates=cands, hw=tpc.hw,
        n_outer=tpc.topology[1],
        cache=plan_mod.default_cache(), comp_hints=comp_hints)
    return plan.num_microbatches, pairer


def _bwd_planner(tpc: TPContext, tg: "df.TrainingGraph", weights, x,
                 mb: int, hints: Optional[Dict[str, float]]):
    """Pass-3 planner for the merged fwd+bwd training graph. ``"greedy"``
    keeps the deterministic nearest-pair policy (None). ``"perfsim"`` builds
    a fresh :class:`repro.plan.PerfsimPlanner` over the training graph's
    value shapes (per-chain ``x`` AND cotangent seeds) and the weight table
    extended with the derived transposed keys, with backward attention-core
    adjoints hinted at 2× forward FLOPs."""
    if tpc.planner != "perfsim":
        return None
    from repro import plan as plan_mod

    b_loc = max(int(x.shape[0]) // max(sharding.dp_size(tpc.mesh), 1), 1)
    per = (max(b_loc // mb, 1), int(x.shape[1]), int(x.shape[2]))
    chains = ["x"] if mb == 1 else [f"mb{i}.x" for i in range(mb)]
    vshapes = {c: per for c in chains}
    # cotangent seeds are activation-shaped except the MoE aux-loss
    # statistics, which are scalar side-outputs
    vshapes.update({gi: ((1,) if gi.endswith("aux") else per)
                    for gi in tg.grad_inputs})
    wshapes = {k: tuple(v.shape) for k, v in weights.items()}
    wshapes.update(df.derived_weight_shapes(tg.graph, wshapes))
    bh = {}
    for k, f in (hints or {}).items():
        for pfx in ([""] if mb == 1 else [f"mb{i}." for i in range(mb)]):
            bh[pfx + k] = f / mb
            bh["adj." + pfx + k] = 2.0 * f / mb
    return plan_mod.PerfsimPlanner(
        value_shapes=vshapes, weight_shapes=wshapes,
        dtype_bytes=np.dtype(x.dtype).itemsize,
        fabric=plan_mod.fabric_from_hw(tpc.hw, max(tpc.tp, 2),
                                       n_outer=tpc.topology[1]),
        backend=tpc.mode, num_microbatches=mb,
        cache=plan_mod.default_cache(), comp_hints=bh or None)


# op-sets already warned about, so a training loop re-tracing the same
# period shape doesn't repeat the message every step
_GRAPH_BWD_WARNED: set = set()


def _warn_graph_bwd_fallback(bad_ops: Sequence[str]) -> None:
    """Warn ONCE per offending op-set when ``graph_backward=True`` cannot
    build the backward graph and falls back to JAX autodiff of the executed
    forward — naming the ops without adjoints so the fallback is never
    silent."""
    key = tuple(bad_ops)
    if key in _GRAPH_BWD_WARNED:
        return
    _GRAPH_BWD_WARNED.add(key)
    warnings.warn(
        "TPConfig(graph_backward=True): period graph has no declared "
        f"adjoint for op(s) {', '.join(repr(o) for o in bad_ops)}; "
        "falling back to JAX autodiff of the executed forward "
        "(docs/training.md)", UserWarning, stacklevel=3)


def sp_period(tpc: TPContext, x, params_seq, cfg, kinds: Sequence[str], *,
              opts: Optional[SPOptions] = None, **kw):
    """A whole ``layer_pattern`` period — every block in ``kinds`` with its
    params from ``params_seq`` — built as ONE dataflow graph, optimized, and
    executed in ONE ``shard_map``. This is the unit the paper's graph-level
    optimizer actually evaluates: with ≥2 blocks, pass 2 fuses the
    block→block seam (block k's FFN-out RS → residual → block k+1's LN1 →
    QKV shared gather, and the MoE rs → residual → ln → route variant) that
    no per-block graph can see, and pass 3's deterministic
    nearest-pair policy co-schedules whatever independent RS/AG pairs the
    merged graph exposes. Options via ``opts`` / :class:`SPOptions`
    keywords.

    ``num_microbatches`` (default: the :class:`TPContext` knob; ``"auto"``
    → :func:`resolve_microbatches`) splits the batch axis into that many
    independent per-microbatch chains merged into the SAME graph with
    shared weights — a straight-line period is fully serialized after
    pass-2 fusion, so this split is what gives pass 3 the independent
    cross-chain pairs it turns into ``overlap_asym`` in the model path.
    The split, per-chain execution, and output re-concatenation all happen
    inside the one ``shard_map``. Block OUTPUTS are exactly preserved
    (≤1e-6, pinned in ``multidev_checks``). The MoE aux loss is NOT: each
    chain routes with its own capacity and the load-balance statistic is
    not linear over sub-batches, so the split period reports the mean of
    per-chain aux values, which differs from the full-batch statistic.
    ``"auto"`` therefore never splits an MoE period — an explicit integer
    is the opt-in that accepts the changed aux term.

    When ``tpc.graph_backward`` is set (the default) and every op of the
    pass-2-fused period declares an adjoint
    (:func:`repro.core.dataflow.supports_backward`), execution is wrapped in
    ``jax.custom_vjp``: the backward is BUILT as a dataflow graph too
    (:func:`repro.core.dataflow.build_training_graph` over the pass-2-fused
    forward), optimized by the same pass-3 planner, and executed in one
    backward ``shard_map`` — so with ``num_microbatches ≥ 2`` pass 3 pairs
    one chain's backward grad reduce-scatter against another chain's
    forward-recompute gather (``overlap_asym`` spanning fwd and bwd), the
    overlap class the paper wins its training speedup from. This covers
    MoE periods (``route``/``a2a_ffn``/``unroute`` adjoints, with the
    aux-loss cotangent seeded per chain) and the replicated-activation
    decode/ragged layout (``seq_sharded=False``: ``gemm_col``/``gemm_ar``
    adjoints, S=1 included). A period whose graph still carries an op with
    no adjoint falls back to JAX autodiff of the executed forward with a
    once-per-op-set ``UserWarning`` naming the ops; the non-explicit
    ``auto`` backend always takes the autodiff path (there is no explicit
    backward schedule to build for it). See ``docs/training.md``.

    x: (B, S, d), sequence-sharded when ``seq_sharded`` (the training path)
    or replicated when not (the decode/ragged-S allreduce path, dense blocks
    only). Returns (period output, summed aux loss)."""
    o = _sp_opts(opts, kw)
    norm_kind = o.norm_kind
    dtype = x.dtype
    M = tpc.tp_axes
    base, weights, specs, aux_vals = _period_graph(
        tpc, params_seq, cfg, kinds, prefix_len=o.prefix_len, dtype=dtype,
        seq_sharded=o.seq_sharded)
    b_loc = max(int(x.shape[0]) // max(sharding.dp_size(tpc.mesh), 1), 1)
    hints = _core_comp_hints(cfg, kinds, b_loc, int(x.shape[1]))
    mb, planner = _plan_period(tpc, base, weights, x, o.num_microbatches,
                               moe=bool(aux_vals), comp_hints=hints)
    merged = microbatch_period_graph(base, mb)
    graph = df.optimize(merged, planner=planner)
    names = list(weights)
    n_aux = len(aux_vals)

    def local(x, *ws):
        with jax.named_scope("tp.period"):
            wmap = dict(zip(names, ws))
            if mb == 1:
                res = df.execute(graph, {"x": x}, wmap, axis=M,
                                 cais=tpc.cais, norm=norm_kind,
                                 backend=tpc.backend)
                if n_aux:
                    # aux leaves the shard_map sharded over (batch, model):
                    # the per-shard statistics genuinely differ per data
                    # shard, so a replicated out-spec would be a lie
                    # (check_vma=False never verifies it) and its autodiff
                    # transpose ill-defined
                    res = tuple(res[:1]) + tuple(a[None] for a in res[1:])
                return res
            res = df.execute(
                graph,
                {f"mb{i}.x": xi
                 for i, xi in enumerate(jnp.split(x, mb, axis=0))},
                wmap, axis=M, cais=tpc.cais, norm=norm_kind,
                backend=tpc.backend)
            per = 1 + n_aux
            out = jnp.concatenate([res[i * per] for i in range(mb)], axis=0)
            auxes = tuple(
                (sum(res[i * per + 1 + j] for i in range(mb)) / mb)[None]
                for j in range(n_aux))
            return (out,) + auxes

    x_spec = (BATCH, M, None) if o.seq_sharded else (BATCH, None, None)
    in_specs = [x_spec] + [specs[k] for k in names]
    out_specs = [x_spec] + [(BATCH, M)] * n_aux
    fwd_call = _smap(tpc, local, in_specs, out_specs)

    use_graph_bwd = (tpc.graph_backward
                     and getattr(tpc.backend, "explicit", True))
    if use_graph_bwd:
        # the backward is declared against the pass-2-fused forward (it
        # re-exposes every activation the adjoints need); pass 3 then runs
        # on the MERGED fwd+bwd graph so pairing can span both directions
        g2 = df.fuse_sublayer_chain(df.fuse_shared_gather(
            df.fuse_compute_aware(merged)))
        bad = sorted({n.op for n in g2.nodes if n.op not in df.ADJOINTS})
        if bad:
            _warn_graph_bwd_fallback(bad)
            use_graph_bwd = False
    if not use_graph_bwd:
        res = fwd_call(x, *weights.values())
        aux = jnp.float32(0.0)
        for a in res[1:]:
            aux = aux + jnp.mean(a)
        return res[0], aux

    tg = df.build_training_graph(g2, norm=norm_kind)
    bwd_graph = df.optimize(tg.graph, planner=_bwd_planner(
        tpc, tg, weights, x, mb, hints))
    chains = ["x"] if mb == 1 else [f"mb{i}.x" for i in range(mb)]
    # weight grads leave the shard_map through specs that omit the batch
    # axes (and MODEL for replicated weights), so the partial sums must be
    # completed inside
    batch_axes = tuple(a for a in _BATCH_AXES
                       if a in tpc.mesh.axis_names)
    # every TP mesh axis a weight's spec does NOT mention replicates that
    # weight there, so its gradient partial-sums must psum over it — on a
    # 2D mesh this is how grouped-EP expert grads reduce over tp_in only
    tp_names = M if isinstance(M, tuple) else (M,)
    tp_names = tuple(a for a in tp_names if a in tpc.mesh.axis_names)
    grad_psum_axes = {}
    for k in names:
        if not o.seq_sharded:
            # replicated-activation layout (decode/ragged): every device
            # sees the full batch×seq, so replicated-weight grads are
            # already complete — a psum over the TP axes would overcount
            # by the ring size
            grad_psum_axes[k] = ()
            continue
        mentioned = set()
        for e in specs[k]:
            if isinstance(e, (tuple, list)):
                mentioned.update(e)
            elif e is not None:
                mentioned.add(e)
        grad_psum_axes[k] = tuple(a for a in tp_names if a not in mentioned)

    def local_bwd(x, gy, *rest):
        with jax.named_scope("tp.period"):
            gauxes, ws = rest[:n_aux], rest[n_aux:]
            wmap = df.derived_weights(bwd_graph, dict(zip(names, ws)))
            vals = {}
            xs = jnp.split(x, mb, axis=0) if mb > 1 else [x]
            gys = jnp.split(gy, mb, axis=0) if mb > 1 else [gy]
            vals.update(zip(chains, xs))
            # cotangent seeds in graph-output order: per chain (d.out,
            # d.aux...). The fwd reports the mean of per-chain aux values, so
            # each chain's aux seed carries 1/mb of the aux cotangent; gauxes
            # arrive (batch, model)-sharded, so ga[0] is exactly this device's
            # slice of the aux cotangent — no replication ambiguity.
            seeds = []
            for i in range(mb):
                seeds.append(gys[i])
                seeds.extend(ga[0] / mb for ga in gauxes)
            vals.update(zip(tg.grad_inputs, seeds))
            res = df.execute(bwd_graph, vals, wmap, axis=M, cais=tpc.cais,
                             norm=norm_kind, backend=tpc.backend)
            got = dict(zip(bwd_graph.outputs, res))
            dxs = [got[tg.dx[c]] for c in chains]
            dx = jnp.concatenate(dxs, axis=0) if mb > 1 else dxs[0]
            dws = []
            for k, w in zip(names, ws):
                parts = [got[v] for v in tg.dweights.get(k, ())]
                dw = parts[0] if parts else jnp.zeros_like(w)
                for p_ in parts[1:]:
                    dw = dw + p_
                if batch_axes:
                    dw = jax.lax.psum(dw, batch_axes)
                if grad_psum_axes[k]:
                    dw = jax.lax.psum(dw, grad_psum_axes[k])
                dws.append(dw.astype(w.dtype))
            return (dx.astype(x.dtype),) + tuple(dws)

    bwd_call = _smap(tpc, local_bwd,
                     [x_spec, x_spec] + [(BATCH, M)] * n_aux
                     + [specs[k] for k in names],
                     [x_spec] + [specs[k] for k in names])

    @jax.custom_vjp
    def period(x, *ws):
        return fwd_call(x, *ws)

    def period_fwd(x, *ws):
        return fwd_call(x, *ws), (x, ws)

    def period_bwd(saved, gys):
        xr, ws = saved
        out = bwd_call(xr, gys[0], *gys[1:], *ws)
        return (out[0],) + tuple(out[1:])

    period.defvjp(period_fwd, period_bwd)
    res = period(x, *tuple(weights.values()))
    aux = jnp.float32(0.0)
    for a in res[1:]:
        aux = aux + jnp.mean(a)
    return res[0], aux


def _serve_attention_core_fn(cfg, tp: int, window: int = 0,
                             axis=MODEL) -> Callable:
    """The paged-serving attention core as a multi-output ``custom`` IR node
    fn: besides q/k/v it takes the :class:`repro.models.attention.KVView`
    arrays (block tables, positions, context lens) and this block's KV pools
    as graph *inputs*, scatters the step's K/V through the block tables,
    attends over each row's gathered context, and returns the updated pools
    as extra outputs — the same multi-output convention as the MoE ``route``
    node. KV-head handling mirrors :func:`_attention_core_fn`: sharded pools
    hold this device's heads; replicated (GQA) pools are written identically
    on every device and sliced per-device for the core."""
    from repro.models.attention import (attention_core, paged_lookup,
                                        paged_update)
    from repro.models.layers import apply_rope

    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    kv_sharded = Hkv % tp == 0

    def core(q, k, v, bt, qpos, ctx, kp, vp):
        B_, S = q.shape[0], q.shape[1]
        H_loc = max(H // tp, 1)
        Hkv_loc = max(Hkv // tp, 1) if kv_sharded else Hkv
        pos = jnp.maximum(qpos, 0)
        q = apply_rope(q.reshape(B_, S, H_loc, dh), pos, cfg.rope_theta)
        k = apply_rope(k.reshape(B_, S, Hkv_loc, dh), pos, cfg.rope_theta)
        v = v.reshape(B_, S, Hkv_loc, dh)
        kp, vp = paged_update(kp, vp, k, v, bt, qpos)
        kk, vv, kv_pos = paged_lookup(kp, vp, bt, ctx)
        if not kv_sharded:
            g = H // Hkv                    # q heads per kv head
            need = max(H_loc // g, 1)
            start = (sharding.shard_map_axis_index(axis) * H_loc) // g
            kk = jax.lax.dynamic_slice_in_dim(kk, start, need, axis=2)
            vv = jax.lax.dynamic_slice_in_dim(vv, start, need, axis=2)
        o = attention_core(q, kk, vv, q_positions=qpos, kv_positions=kv_pos,
                           causal=True, window=window)
        return o.reshape(B_, S, H_loc * dh), kp, vp

    return core


def _serve_block_fragment(tpc: TPContext, params, cfg, kind: str, idx: int,
                          src: str, dtype=jnp.float32):
    """One dense block as a serve-period graph fragment (replicated
    activations, allreduce schedule): like :func:`_block_graph_fragment`
    with ``seq_sharded=False``, except the attention core is the
    pool-carrying :func:`_serve_attention_core_fn` node. Returns
    (nodes, out_value, weights, specs)."""
    p = f"b{idx}."
    tp = tpc.tp
    M = tpc.tp_axes
    m = params["mixer"]
    kv_sharded = cfg.num_kv_heads % tp == 0
    window = cfg.window if kind == "swa" else 0
    core = _serve_attention_core_fn(cfg, tp, window=window, axis=M)

    kv_spec = (None, M) if kv_sharded else (None, None)
    weights = {
        p + "scale1": params["norm1"]["scale"].astype(dtype),
        p + "wq": m["wq"].astype(dtype), p + "wk": m["wk"].astype(dtype),
        p + "wv": m["wv"].astype(dtype), p + "wo": m["wo"].astype(dtype),
        p + "scale2": params["norm2"]["scale"].astype(dtype),
    }
    specs = {
        p + "scale1": (None,), p + "wq": (None, M), p + "wk": kv_spec,
        p + "wv": kv_spec, p + "wo": (M, None), p + "scale2": (None,),
    }
    nodes = [
        df.Node(f"{p}ln1", "layernorm", (src,), (f"{p}scale1",)),
        df.Node(f"{p}q", "gemm_col", (f"{p}ln1",), (f"{p}wq",)),
        df.Node(f"{p}k", "gemm_col", (f"{p}ln1",), (f"{p}wk",)),
        df.Node(f"{p}v", "gemm_col", (f"{p}ln1",), (f"{p}wv",)),
        df.Node(f"{p}o", "custom",
                (f"{p}q", f"{p}k", f"{p}v", "bt", "qpos", "ctx",
                 f"{p}kp", f"{p}vp"),
                outputs=(f"{p}o", f"{p}kpn", f"{p}vpn"), fn=core),
        df.Node(f"{p}proj", "gemm_row", (f"{p}o",), (f"{p}wo",)),
        df.Node(f"{p}rs1", "allreduce", (f"{p}proj",)),
        df.Node(f"{p}r1", "residual", (f"{p}rs1", src)),
        df.Node(f"{p}ln2", "layernorm", (f"{p}r1",), (f"{p}scale2",)),
    ]
    f = params["ffn"]
    has_gate = "w_gate" in f
    nodes += _ffn_chain_nodes(f"{p}ln2", f"{p}rs2", has_gate, cfg.act,
                              tag="2", p=p, seq_sharded=False)
    nodes.append(df.Node(f"{p}r2", "residual", (f"{p}rs2", f"{p}r1")))
    weights[p + "w_up"] = f["w_up"].astype(dtype)
    specs[p + "w_up"] = (None, M)
    if has_gate:
        weights[p + "w_gate"] = f["w_gate"].astype(dtype)
        specs[p + "w_gate"] = (None, M)
    weights[p + "w_down"] = f["w_down"].astype(dtype)
    specs[p + "w_down"] = (M, None)
    return nodes, f"{p}r2", weights, specs


def sp_serve_period(tpc: TPContext, x, params_seq, cfg,
                    kinds: Sequence[str], pools_seq, view, *,
                    norm_kind: str = "rmsnorm"):
    """A whole period of a mixed prefill+decode *serving* step as ONE
    dataflow graph in ONE ``shard_map`` — the serving analogue of
    :func:`sp_period`. The activation stays replicated (decode S=1 and
    chunked-prefill S % tp ≠ 0 both fit), so pass 1 fuses every
    out-projection/FFN-down reduction into backend-dispatched ``gemm_ar`` —
    TP is never silently unsharded under serving. The paged KV pools, block
    tables, and position/context arrays enter the graph as extra inputs of
    each block's attention ``custom`` node, and the updated pools leave as
    graph outputs. With ``planner="perfsim"`` the optimized schedule comes
    from the simulated-makespan search over the serve-period graph itself
    (value shapes include the real pool/table shapes), through the plan
    cache. Pools are shared unbatched state: callers must run with dp == 1
    (gated in ``models.transformer._blocks_step``).

    x: (B, S_step, d) replicated; ``pools_seq`` one ``{"k", "v"}`` pool dict
    per block; ``view`` a :class:`repro.models.attention.KVView`. Returns
    (period output, new pools list)."""
    dtype = x.dtype
    n = len(kinds)
    nodes = [df.Node("x", "input"), df.Node("bt", "input"),
             df.Node("qpos", "input"), df.Node("ctx", "input")]
    weights: Dict[str, jnp.ndarray] = {}
    specs: Dict[str, tuple] = {}
    src = "x"
    for i, (params, kind) in enumerate(zip(params_seq, kinds)):
        nodes += [df.Node(f"b{i}.kp", "input"), df.Node(f"b{i}.vp", "input")]
        ns, src, w, s = _serve_block_fragment(tpc, params, cfg, kind, i, src,
                                              dtype=dtype)
        nodes += ns
        weights.update(w)
        specs.update(s)
    pool_outs = tuple(v for i in range(n)
                      for v in (f"b{i}.kpn", f"b{i}.vpn"))
    base = df.Graph(nodes, outputs=(src,) + pool_outs)

    planner = None
    b_loc = max(int(x.shape[0]) // max(sharding.dp_size(tpc.mesh), 1), 1)
    hints = _core_comp_hints(cfg, kinds, b_loc, int(x.shape[1]))
    if tpc.planner == "perfsim":
        from repro import plan as plan_mod

        vshapes = {"x": (b_loc,) + tuple(int(d) for d in x.shape[1:]),
                   "bt": tuple(view.block_tables.shape),
                   "qpos": tuple(view.positions.shape),
                   "ctx": tuple(view.context_lens.shape)}
        for i, pool in enumerate(pools_seq):
            vshapes[f"b{i}.kp"] = tuple(pool["k"].shape)
            vshapes[f"b{i}.vp"] = tuple(pool["v"].shape)
        planner = plan_mod.PerfsimPlanner(
            value_shapes=vshapes,
            weight_shapes={k: tuple(v.shape) for k, v in weights.items()},
            dtype_bytes=np.dtype(x.dtype).itemsize,
            fabric=plan_mod.fabric_from_hw(tpc.hw, max(tpc.tp, 2),
                                           n_outer=tpc.topology[1]),
            backend=tpc.mode, cache=plan_mod.default_cache(),
            comp_hints=hints)
    graph = df.optimize(base, planner=planner)
    names = list(weights)

    def local(x, bt, qpos, ctx, *rest):
        pools, ws = rest[:2 * n], rest[2 * n:]
        vals = {"x": x, "bt": bt, "qpos": qpos, "ctx": ctx}
        for i in range(n):
            vals[f"b{i}.kp"] = pools[2 * i]
            vals[f"b{i}.vp"] = pools[2 * i + 1]
        return df.execute(graph, vals, dict(zip(names, ws)),
                          axis=tpc.tp_axes, cais=tpc.cais, norm=norm_kind,
                          backend=tpc.backend)

    kv_sharded = cfg.num_kv_heads % tpc.tp == 0
    pool_spec = (None, None, tpc.tp_axes, None) if kv_sharded \
        else (None, None, None, None)
    x_spec = (BATCH, None, None)
    in_specs = ([x_spec, (None, None), (None, None), (None,)]
                + [pool_spec] * (2 * n) + [specs[k] for k in names])
    out_specs = [x_spec] + [pool_spec] * (2 * n)
    flat_pools = [p[kk] for p in pools_seq for kk in ("k", "v")]
    res = _smap(tpc, local, in_specs, out_specs)(
        x, view.block_tables, view.positions, view.context_lens,
        *flat_pools, *weights.values())
    new_pools = [{"k": res[1 + 2 * i], "v": res[2 + 2 * i]}
                 for i in range(n)]
    return res[0], new_pools


def sp_block(tpc: TPContext, x, params, cfg, kind: str = "attn", *,
             opts: Optional[SPOptions] = None, **kw):
    """A whole pre-norm transformer block — attention residual → FFN/MoE
    residual — as a single-period special case of :func:`sp_period` (the
    documented entry point for one block): ONE dataflow graph, optimized,
    executed in ONE ``shard_map``. The graph spans the attention-out →
    FFN-in seam, so pass 2 fuses RS → residual → LN → AG into one pipeline
    on every dense block and MoE routing flows through the same IR.

    ``params`` is the block param dict from ``models.transformer.init_block``
    (``norm1``/``mixer``/``norm2``/``ffn``). x: (B, S, d) sequence-sharded
    (or replicated with ``seq_sharded=False`` — the decode-style allreduce
    schedule). Options via ``opts`` / :class:`SPOptions` keywords. Returns
    (block output, aux loss)."""
    return sp_period(tpc, x, (params,), cfg, (kind,),
                     opts=_sp_opts(opts, kw))


def tp_applicable(cfg, kind: str, tp: int,
                  route_ring: Optional[int] = None) -> bool:
    """Explicit-backend shard_map path requires Q-head and feature
    divisibility (KV heads may replicate); otherwise the block stays on the
    `auto` path (DESIGN.md §5). ``route_ring`` is the expert-sharding ring
    (``tp`` on a flat mesh; ``tp_out`` on a hierarchical 2D mesh — pass
    ``TPContext.route_ring``)."""
    if kind in ("attn", "swa"):
        return cfg.num_heads % tp == 0 and cfg.norm == "rmsnorm"
    if kind == "ffn":
        return cfg.moe is None and cfg.d_ff > 0 and cfg.d_ff % tp == 0 \
            and cfg.norm == "rmsnorm"
    if kind == "moe":
        # integrated path requires true EP over the route ring: with
        # E < ring the owner mapping works (primitive-level tests) but
        # replicated expert weights turn their gradients into a full-size
        # all-reduce — measured regression, EXPERIMENTS.md §Perf cell 2.
        # Grouped EP (docs/topology.md) is the production fix: on a 2D
        # mesh the ring is only ``tp_out``, so E < tp archs qualify
        # whenever E % tp_out == 0 (expert grads psum over tp_in, the
        # fast intra-node links).
        ring = tp if route_ring is None else route_ring
        return cfg.moe is not None and cfg.norm == "rmsnorm" and \
            cfg.moe.num_experts % ring == 0
    return False
