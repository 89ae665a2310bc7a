"""Graph-level dataflow optimizer (paper §III-C).

A small dataflow IR over TP transformer blocks — and, since the period-level
refactor, over whole ``layer_pattern`` *periods* (≥1 blocks chained into one
graph, see :func:`repro.core.tp.sp_period`) — plus fusion passes.

Op vocabulary (and which optimizer pass consumes each op)
---------------------------------------------------------

Primitive ops (emitted by the graph builders in :mod:`repro.core.tp`):

``input``
    Declares a graph value. Consumed by no pass.
``gemm_col`` / ``gemm_row``
    Column-/row-sharded projection. Pass 1 (``fuse_compute_aware``) aligns
    the adjacent collective with the GEMM's memory semantics; pass 1b
    (``fuse_shared_gather``) merges several ``gemm_col`` consumers of one
    gather into ``ag_gemm_multi``.
``allgather`` / ``reduce_scatter`` / ``allreduce``
    The raw collectives. Consumed by pass 1/1b into the fused forms below.
``layernorm``
    Sequence-parallel norm (no collective). Consumed by pass 2
    (``fuse_sublayer_chain``) when it sits on an rs→ln→ag seam.
``add`` / ``residual``
    Elementwise sum; ``residual`` marks the block's residual connection
    (main branch first, skip second). Pass 2 folds either into the fused
    chain and re-exposes the post-add value.
``custom``
    Arbitrary *local* math (activation, attention core, dense-residual MLP)
    — it never touches the mesh, so every pass may move collectives
    around it. ``fn(*inputs, *weights)``.
``route`` / ``unroute``
    Top-k expert routing: ``route`` turns a normed activation into the
    per-owner send buffer (+ combine weights + aux loss), ``unroute``
    scatters expert outputs back to token order. Local math like
    ``custom`` (multi-output capable); no pass rewrites them today — they
    exist as named ops so future passes can schedule the expert all-to-all
    against the dense residual.
``a2a_ffn``
    Expert all-to-all + expert FFN, dispatched through
    ``CollectiveBackend.a2a_expert_ffn`` (the ``cais`` backend overlaps
    ±direction dispatch/combine permutes with the expert GEMMs).

Fused ops (produced by ``optimize``, executed via the backend):

``ag_gemm`` / ``ag_gemm_multi``
    Pull-aligned AllGather→GEMM (one or several weights sharing one ring
    circulation). Produced by pass 1 / 1b; pass 2 and 3 consume them.
``gemm_rs`` / ``gemm_ar``
    Push-aligned GEMM→ReduceScatter / →AllReduce. Produced by pass 1;
    pass 2 and 3 consume ``gemm_rs``.
``fused_rs_ln_ag`` / ``fused_rs_ln_ag_multi``
    Deep fusion of the ``gemm_rs → [add|residual] → layernorm →
    ag_gemm[_multi]`` sub-layer seam (Fig. 9) — the whole-block graph's
    attention-out → FFN-in chain, and (in a period graph) the block→block
    seam: block k's FFN-out RS → residual → block k+1's LN1 → QKV shared
    gather. Produced by pass 2 (terminal).
``fused_rs_ln``
    The gather-less prefix of the same seam: ``gemm_rs → [add|residual] →
    layernorm`` whose normed value feeds a ``route`` node (the MoE
    attention-out → router seam) — the trailing collective is the expert
    all-to-all, not an allgather, so only the RS + add + norm fuse. Outputs
    ``(normed, z)``; produced by pass 2 (terminal), executed via
    ``CollectiveBackend.fused_rs_ln``.
``overlap_asym``
    Co-scheduled independent ``gemm_rs`` + ``ag_gemm[_multi]`` pair with
    complementary ring directions (asymmetric kernel overlapping,
    Fig. 9e/10). Produced by pass 3 (``pair_asymmetric``, terminal).
    Pairing is deterministic and nearest-independent-pair-first: candidate
    pairs are ranked by topological distance (ties: earliest position, then
    node names), so a merged microbatch/period graph picks the adjacent
    seam — one chain's FFN-out RS against the *nearest* independent
    attention gather — rather than an arbitrary first match. Candidates
    must come from *different* chains (disjoint ``input``-ancestor sets):
    two collectives fed by the same microbatch's data never pair, even when
    a fork makes them dependency-free, so a chain is never lockstep-
    serialized against itself.

``bwd_ag_gemm``
    Backward-only: the adjoint of ``gemm_rs`` — AllGather the seq-sharded
    output cotangent, GEMM with the transposed weight, and re-expose the
    gathered cotangent for the weight-gradient GEMM. Emitted by
    :func:`build_training_graph` (never by the forward builders), executed
    via ``CollectiveBackend.grad_ag_gemm``.
``bwd_a2a_ffn``
    Backward-only: the adjoint of ``a2a_ffn`` — re-dispatch the forward
    send buffer together with the output cotangent to each expert owner
    (forward-direction all-to-all), run the per-row VJP of the expert FFN
    there, return ``d(recv)`` to the senders (reverse all-to-all) and keep
    the local expert-weight grads on the owner. Emitted by
    :func:`build_training_graph`, executed via
    ``CollectiveBackend.grad_a2a_expert_ffn`` (the ``cais`` backend
    interleaves the ±offset dispatch/return permutes with the VJP GEMMs;
    the hierarchical composition keeps grouped-EP grads off the fast
    ``tp_in`` axis).

A worked trace of a 2-block period through every pass lives in
``docs/architecture.md``; ``docs/backends.md`` documents the backend methods
each fused op dispatches to; ``docs/training.md`` documents the
backward-graph builder (:func:`build_training_graph`) and the per-op
adjoint table (``ADJOINTS``).

The executor runs a graph either as pure math (no mesh; reference) or inside
``shard_map`` (explicit TP), dispatching every fused collective op through a
:class:`repro.core.backends.CollectiveBackend` — the model blocks
(``repro.core.tp.sp_block`` and the per-sub-layer ``sp_ffn`` /
``sp_attention``) are built, optimized, and run through this IR. Tensor
layout conventions per value: ``seq`` (B, S_loc, d) sequence-sharded ·
``feat`` (B, S, d_loc) feature-sharded · ``full`` (B, S, d) replicated.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.primitives import CAISConfig

# ---------------------------------------------------------------------------
# IR
# ---------------------------------------------------------------------------

#        op            inputs                weights         out layout
# input                ()                    —               declared
# gemm_col             (x: full)             w (d, F/n)      feat
# gemm_row             (x: feat)             w (d/n, F)      partial-full
# allgather            (x: seq)              —               full
# reduce_scatter       (x: partial-full)     —               seq
# allreduce            (x: partial-full)     —               full
# layernorm            (x: any)              scale (d,)      same
# add / residual       (a, b) same layout    —               same
# custom               (any...)              (w...)          fn-defined
# route                (xn: seq)             (router,)       (send, combine,
#                                                             aux)
# unroute              (eout, combine, xn)   —               seq
# a2a_ffn              (send,)               (expert ws...)  send-shaped
# --- fused (produced by optimize) ---
# ag_gemm              (x: seq)              w               feat
# ag_gemm_multi        (x: seq)              (w...)          feat per weight
# gemm_rs              (x: feat)             w               seq
# gemm_ar              (x: feat)             w               full
# fused_rs_ln_ag       (x: feat[, res:seq])  (w1, scale, w2) feat (+ seq z)
# fused_rs_ln_ag_multi (x: feat[, res:seq])  (w1, scale, w...) feat per w
#                                                             (+ seq z)
# fused_rs_ln          (x: feat[, res:seq])  (w1, scale)     (seq zn, seq z)
# overlap_asym         (x_rs: feat, x_ag: seq) (w_rs, w_ag...) (seq, feat...)
# bwd_ag_gemm          (dy: seq)             wT (d, F/n)     (feat dx, full dy)
# bwd_a2a_ffn          (send, dy) send-shaped (expert ws...)  (d_send, dw...)

VALID_OPS = {
    "input", "gemm_col", "gemm_row", "allgather", "reduce_scatter",
    "allreduce", "layernorm", "add", "residual", "custom",
    "route", "unroute", "a2a_ffn",
    "ag_gemm", "ag_gemm_multi", "gemm_rs", "gemm_ar", "fused_rs_ln_ag",
    "fused_rs_ln_ag_multi", "fused_rs_ln", "overlap_asym",
    "bwd_ag_gemm", "bwd_a2a_ffn",
}

# Declared adjoint vocabulary (docs/training.md): the backward-graph builder
# (:func:`build_training_graph`) knows how to emit adjoint nodes for exactly
# these forward ops — every op the model builders can leave in a period
# graph after passes 1/1b/2, MoE routing and the ragged/decode layouts
# included. Each entry maps a forward op to the IR ops its adjoint emits, so
# the backward is itself a dataflow graph the optimizer (and the perfsim
# planner) schedules: ``ag_gemm[_multi]`` ↔ a grad reduce-scatter
# (``gemm_rs`` over the transposed weight), ``gemm_rs`` ↔ a grad all-gather
# (``bwd_ag_gemm``), ``fused_rs_ln_ag[_multi]`` / ``fused_rs_ln`` ↔ the
# fused composition of both around the norm's VJP, ``a2a_ffn`` ↔ the
# reverse expert all-to-all (``bwd_a2a_ffn``), ``route``/``unroute`` ↔
# local ``jax.vjp`` of the routing closures (the aux-loss side-output's
# cotangent seeds the router-logit grads), ``gemm_ar`` ↔ purely local math
# (its output is replicated, so dx/dw need no collective), ``gemm_col`` ↔ a
# grad allreduce (a backward ``gemm_ar`` over the transposed weight — the
# sequence-parallel-off layout's backbone). Graphs containing any other op
# (raw collectives, pass-3 ``overlap_asym``) report
# ``supports_backward() == False`` and keep JAX autodiff of the executed
# forward graph.
ADJOINTS = {
    "input": (),
    "add": (), "residual": (),              # gradient fan-out, no new nodes
    "layernorm": ("custom",),               # norm VJP (local math)
    "custom": ("custom",),                  # jax.vjp of the node's fn
    "route": ("custom",),                   # jax.vjp of the routing closure
    "unroute": ("custom",),                 # the route adjoint's dual
    "a2a_ffn": ("bwd_a2a_ffn",),            # reverse expert all-to-all
    "ag_gemm": ("custom", "gemm_rs", "allgather"),
    "ag_gemm_multi": ("custom", "gemm_rs", "allgather"),
    "gemm_rs": ("bwd_ag_gemm", "custom"),
    "gemm_ar": ("custom",),                 # replicated out: local dx/dw
    "gemm_col": ("gemm_ar", "custom"),      # grad allreduce through w^T
    "fused_rs_ln_ag": ("custom", "gemm_rs", "bwd_ag_gemm", "allgather"),
    "fused_rs_ln_ag_multi": ("custom", "gemm_rs", "bwd_ag_gemm",
                             "allgather"),
    "fused_rs_ln": ("custom", "bwd_ag_gemm"),
}

# local-math ops whose semantics live in the node's `fn`
_FN_OPS = ("custom", "route", "unroute")


class GraphError(ValueError):
    """A malformed dataflow graph: unknown op, cycle, missing producer…
    Always names the offending node/value."""


@dataclass(frozen=True)
class Node:
    name: str
    op: str
    inputs: Tuple[str, ...] = ()
    weights: Tuple[str, ...] = ()   # keys into the weights dict
    outputs: Tuple[str, ...] = ()   # multi-output fused ops; default (name,)
    fn: Optional[Callable] = None   # local math for fn-carrying ops

    def __post_init__(self):
        if self.op not in VALID_OPS:
            raise GraphError(
                f"node {self.name!r} has unknown dataflow op {self.op!r}; "
                f"valid ops: {sorted(VALID_OPS)}")
        if not self.outputs:
            object.__setattr__(self, "outputs", (self.name,))


@dataclass
class Graph:
    nodes: List[Node]
    outputs: Tuple[str, ...]
    # lazily-built adjacency index shared by node_producing / consumers /
    # reaches. Passes never mutate a Graph in place (every rewrite builds a
    # fresh Graph), so the cache stays valid for the instance's lifetime.
    _idx: Optional[Tuple[Dict[str, Node], Dict[str, List[Node]],
                         Dict[str, Node]]] = field(
        default=None, init=False, repr=False, compare=False)

    def _index(self):
        if self._idx is None:
            producer: Dict[str, Node] = {}
            consumers: Dict[str, List[Node]] = {}
            by_name: Dict[str, Node] = {}
            for n in self.nodes:
                by_name[n.name] = n
                for v in n.outputs:
                    producer[v] = n
                for v in n.inputs:
                    consumers.setdefault(v, []).append(n)
            self._idx = (producer, consumers, by_name)
        return self._idx

    def node_producing(self, value: str) -> Optional[Node]:
        return self._index()[0].get(value)

    def consumers(self, value: str) -> List[Node]:
        return list(self._index()[1].get(value, ()))

    def reaches(self, src: str, dst: str) -> bool:
        """Is there a dependency path from node `src` to node `dst`?
        O(V+E) per query over the shared adjacency index."""
        _, consumers_of, by_name = self._index()
        seen, stack = set(), [src]
        while stack:
            cur = stack.pop()
            if cur == dst:
                return True
            if cur in seen:
                continue
            seen.add(cur)
            for v in by_name[cur].outputs:
                stack.extend(c.name for c in consumers_of.get(v, ()))
        return False

    def validate(self) -> "Graph":
        """Raise :class:`GraphError` (naming the offender) on missing
        producers, duplicate producers, unknown graph outputs, or cycles."""
        _topo(self.nodes, self.outputs)
        return self


# ---------------------------------------------------------------------------
# Fusion passes
# ---------------------------------------------------------------------------


def _single_consumer(g: Graph, value: str,
                     allow_output: bool = False) -> Optional[Node]:
    """The unique consumer of `value`, or None. A value listed in the graph
    outputs counts as externally consumed unless ``allow_output`` (used when
    the fused op re-exposes the value, e.g. fused_rs_ln_ag's z output)."""
    cs = g.consumers(value)
    if not allow_output and value in g.outputs:
        return None
    return cs[0] if len(cs) == 1 else None


def fuse_compute_aware(g: Graph) -> Graph:
    """Pass 1: align collectives with the adjacent GEMM's memory semantics."""
    nodes = list(g.nodes)
    changed = True
    while changed:
        changed = False
        for n in nodes:
            if n.op == "allgather":
                c = _single_consumer(g, n.name)
                if c is not None and c.op == "gemm_col":
                    fused = Node(c.name, "ag_gemm", n.inputs, c.weights)
                    nodes = [x for x in nodes if x.name not in (n.name, c.name)]
                    nodes.append(fused)
                    g = Graph(_topo(nodes, g.outputs), g.outputs)
                    nodes = list(g.nodes)
                    changed = True
                    break
            if n.op == "gemm_row":
                c = _single_consumer(g, n.name)
                if c is not None and c.op in ("reduce_scatter", "allreduce"):
                    op = "gemm_rs" if c.op == "reduce_scatter" else "gemm_ar"
                    fused = Node(c.name, op, n.inputs, n.weights)
                    nodes = [x for x in nodes if x.name not in (n.name, c.name)]
                    nodes.append(fused)
                    g = Graph(_topo(nodes, g.outputs), g.outputs)
                    nodes = list(g.nodes)
                    changed = True
                    break
    return Graph(_topo(nodes, g.outputs), g.outputs)


def fuse_shared_gather(g: Graph) -> Graph:
    """Pass 1b: an ``allgather`` consumed by *several* ``gemm_col`` nodes
    (fused QKV, gate+up) becomes one ``ag_gemm_multi``: the activation
    circulates the ring once and every weight consumes each arriving chunk
    (the multi-weight pull alignment the hand-fused sub-layers used)."""
    nodes = list(g.nodes)
    for n in nodes:
        if n.op != "allgather" or n.name in g.outputs:
            continue
        cs = g.consumers(n.name)
        if len(cs) < 2 or any(c.op != "gemm_col" for c in cs):
            continue
        fused = Node("+".join(c.name for c in cs), "ag_gemm_multi",
                     n.inputs,
                     tuple(w for c in cs for w in c.weights),
                     outputs=tuple(c.name for c in cs))
        drop = {n.name} | {c.name for c in cs}
        nodes = [x for x in nodes if x.name not in drop] + [fused]
        return fuse_shared_gather(Graph(_topo(nodes, g.outputs), g.outputs))
    return g


def fuse_sublayer_chain(g: Graph) -> Graph:
    """Pass 2: gemm_rs → [add|residual] → layernorm → ag_gemm[_multi] ⇒ one
    pipeline. The post-add value may have *several* consumers (in a
    whole-block graph it feeds both the next LN and the next residual add;
    in a period graph the block→block seam looks the same): the fused op
    re-exposes it, so only the layernorm leg is swallowed.

    MoE variant: when the normed value feeds a ``route`` node instead of a
    gather (attention-out RS → residual → LN → router), the gather-less
    prefix fuses into ``fused_rs_ln``, which re-exposes BOTH the normed
    value (for route/unroute/dense-residual consumers) and z."""
    nodes = list(g.nodes)
    for rs in list(nodes):
        if rs.op != "gemm_rs":
            continue
        # rs's value may escape as a graph output — the fused op re-exposes it
        nxt = _single_consumer(g, rs.name, allow_output=True)
        residual = None
        add_node = None
        if nxt is not None and nxt.op in ("add", "residual"):
            if rs.name in g.outputs:
                # the fused op re-exposes only the post-add z — a graph that
                # also exports the pre-add value must keep the chain unfused
                continue
            other = [v for v in nxt.inputs if v != rs.name]
            residual = other[0] if other else None
            add_node = nxt
            # z = rs + residual is re-exposed by the fused op, so it may be a
            # graph output or feed several consumers — fuse along the (one)
            # layernorm among them
            lns = [c for c in g.consumers(nxt.name) if c.op == "layernorm"]
            nxt = lns[0] if len(lns) == 1 else None
        if nxt is None or nxt.op != "layernorm":
            continue
        ln = nxt
        ins = rs.inputs + ((residual,) if residual else ())
        z_name = (add_node or rs).name
        drop = {rs.name, ln.name} | ({add_node.name} if add_node else set())
        ag = _single_consumer(g, ln.name)
        if ag is not None and ag.op in ("ag_gemm", "ag_gemm_multi"):
            if ag.op == "ag_gemm":
                fused = Node(ag.name, "fused_rs_ln_ag", ins,
                             rs.weights + ln.weights + ag.weights,
                             outputs=(ag.name, z_name))
            else:
                fused = Node(ag.name, "fused_rs_ln_ag_multi", ins,
                             rs.weights + ln.weights + ag.weights,
                             outputs=ag.outputs + (z_name,))
            drop.add(ag.name)
        elif any(c.op == "route" for c in g.consumers(ln.name)):
            # the normed value feeds an expert router (and usually also the
            # unroute scatter / a dense-residual MLP) — fuse the RS + add +
            # norm and re-expose the normed value under its old name
            fused = Node(ln.name, "fused_rs_ln", ins,
                         rs.weights + ln.weights,
                         outputs=(ln.name, z_name))
        else:
            continue
        nodes = [x for x in nodes if x.name not in drop] + [fused]
        return fuse_sublayer_chain(Graph(_topo(nodes, g.outputs), g.outputs))
    return Graph(_topo(nodes, g.outputs), g.outputs)


def _input_ancestors(g: Graph, nodes: List[Node]) -> Dict[str, frozenset]:
    """Node name → the set of graph ``input`` nodes it transitively depends
    on. Two nodes belong to the same microbatch *chain* iff these sets
    intersect: merged microbatch fragments each hang off their own input
    (``mb{i}.x``), so cross-chain sets are disjoint while a fork inside one
    chain shares its input ancestor. ``nodes`` must be in topo order."""
    anc: Dict[str, frozenset] = {}
    for n in nodes:
        if n.op == "input":
            anc[n.name] = frozenset((n.name,))
            continue
        s = frozenset()
        for v in n.inputs:
            p = g.node_producing(v)
            if p is not None:
                s |= anc[p.name]
        anc[n.name] = s
    return anc


def asymmetric_candidates(g: Graph) -> List[Tuple[Node, Node]]:
    """Every legal pass-3 pair of ``g``, ranked nearest-independent-first.

    A candidate is a (gemm_rs, ag_gemm[_multi]) pair with no dependency path
    either way AND disjoint ``input``-ancestor sets (the chain-id guard: the
    overlap primitive runs its streams in lockstep, so two collectives fed
    by the same microbatch's data — dependency-free only because of a fork —
    must never pair). Ranking: topological distance, ties broken by earliest
    topo position and then by node names — the greedy pass takes the head of
    this list; the perfsim planner scores *alternative* orders.

    On a merged fwd+bwd TRAINING graph (one with ``d.*`` cotangent-seed
    inputs, see :func:`build_training_graph`) cross-direction pairs — a
    backward grad reduce-scatter against a forward(-recompute) gather, the
    T3-class overlap the paper targets — rank before same-direction pairs:
    pairing two forward nodes of different chains serializes one chain's
    whole backward behind the other's forward, while the cross pair is the
    schedule that hides the grad collective behind the next chain's
    forward. Forward-only graphs have no seeds, so their ranking (and every
    pre-training behaviour pinned on it) is unchanged."""
    nodes = _topo(list(g.nodes), g.outputs)
    order = {n.name: i for i, n in enumerate(nodes)}
    chain = _input_ancestors(g, nodes)
    seeds = frozenset(n.name for n in nodes
                      if n.op == "input" and n.name.startswith(_D_PREFIX))

    def is_bwd(name: str) -> bool:
        return bool(chain[name] & seeds)

    cands = []
    for a in nodes:
        if a.op != "gemm_rs":
            continue
        for b in nodes:
            if b.op not in ("ag_gemm", "ag_gemm_multi") or b.name == a.name:
                continue
            if chain[a.name] & chain[b.name]:
                continue
            if g.reaches(a.name, b.name) or g.reaches(b.name, a.name):
                continue
            key = (0 if seeds and is_bwd(a.name) != is_bwd(b.name) else 1,
                   abs(order[a.name] - order[b.name]),
                   min(order[a.name], order[b.name]), a.name, b.name)
            cands.append((key, a, b))
    cands.sort(key=lambda t: t[0])
    return [(a, b) for _, a, b in cands]


def apply_pair(g: Graph, a: Node, b: Node) -> Graph:
    """Fuse one (gemm_rs, ag_gemm[_multi]) candidate into ``overlap_asym``."""
    fused = Node(f"{a.name}+{b.name}", "overlap_asym",
                 a.inputs + b.inputs, a.weights + b.weights,
                 outputs=(a.name,) + b.outputs)
    nodes = [x for x in g.nodes if x.name not in (a.name, b.name)]
    nodes.append(fused)
    return Graph(_topo(nodes, g.outputs), g.outputs)


def pair_asymmetric(g: Graph,
                    pairing: Optional[Sequence[Tuple[str, str]]] = None
                    ) -> Graph:
    """Pass 3: co-schedule independent gemm_rs + ag_gemm[_multi] pairs so
    their complementary ring directions share the links each step (e.g. one
    microbatch's FFN-out RS against another's attention-in gather).

    Default policy (deterministic, nearest-independent-pair-first): fuse the
    head of :func:`asymmetric_candidates` and repeat until no independent
    pair remains — a merged microbatch/period graph co-schedules the
    *adjacent* seam (chain k's FFN-out RS with the nearest independent
    attention gather of chain k+1) rather than an arbitrary first match.

    With an explicit ``pairing`` — an ordered sequence of (gemm_rs name,
    ag_gemm name) — the pass instead applies exactly those pairs, in order
    (a planner decision, see :mod:`repro.plan.search`). Each named pair must
    still be a legal candidate when its turn comes (earlier fusions change
    the dependency structure); an illegal pair raises :class:`GraphError`
    so a stale cached plan fails loudly rather than silently reordering."""
    if pairing is not None:
        for rs_name, ag_name in pairing:
            cand = {(a.name, b.name): (a, b)
                    for a, b in asymmetric_candidates(g)}
            if (rs_name, ag_name) not in cand:
                raise GraphError(
                    f"planner pairing ({rs_name!r}, {ag_name!r}) is not a "
                    f"legal independent pair of this graph")
            g = apply_pair(g, *cand[(rs_name, ag_name)])
        return g
    cands = asymmetric_candidates(g)
    if not cands:
        return Graph(_topo(list(g.nodes), g.outputs), g.outputs)
    return pair_asymmetric(apply_pair(g, *cands[0]))


def optimize(g: Graph, asymmetric: bool = True, planner=None) -> Graph:
    """Run passes 1 → 1b → 2 → 3. ``planner`` drives pass 3's pairing order:

    - ``None`` / ``"greedy"`` — the deterministic nearest-independent-first
      policy (the default, unchanged behaviour);
    - ``"perfsim"`` — a :class:`repro.plan.search.PerfsimPlanner` with
      synthesized shapes: candidate pairings are scored by simulated
      makespan over the perfsim cost model and the argmin wins;
    - any object with a ``pair(g) -> Graph`` method — e.g. a PerfsimPlanner
      carrying the real shapes/topology (the ``tp.sp_period`` path).
    """
    g = fuse_compute_aware(g)
    g = fuse_shared_gather(g)
    g = fuse_sublayer_chain(g)
    if asymmetric:
        if planner is None or planner == "greedy":
            g = pair_asymmetric(g)
        else:
            if planner == "perfsim":
                from repro.plan import PerfsimPlanner
                planner = PerfsimPlanner()
            g = planner.pair(g)
    return g


def _topo(nodes: List[Node], outputs) -> List[Node]:
    """Stable topological order by value availability.

    Raises :class:`GraphError` naming the offending node/value on duplicate
    producers, unknown graph outputs, inputs with no producer, or cycles."""
    produced: Dict[str, str] = {}
    for n in nodes:
        for v in n.outputs:
            if v in produced and produced[v] != n.name:
                raise GraphError(
                    f"value {v!r} is produced by both node {produced[v]!r} "
                    f"and node {n.name!r}")
            produced[v] = n.name
    for o in outputs:
        if o not in produced:
            raise GraphError(
                f"graph output {o!r} is not produced by any node")
    avail = set()
    ordered = [n for n in nodes if n.op == "input"]
    for n in ordered:
        avail |= set(n.outputs)
    pending = [n for n in nodes if n.op != "input"]
    while pending:
        ready = [n for n in pending if all(v in avail for v in n.inputs)]
        if not ready:
            # stalled — diagnose: a consumed value nobody produces, or a cycle
            for n in pending:
                missing = [v for v in n.inputs if v not in produced]
                if missing:
                    raise GraphError(
                        f"node {n.name!r} consumes value {missing[0]!r}, "
                        f"which no node produces")
            cyc = ", ".join(sorted(n.name for n in pending))
            raise GraphError(
                f"cycle in dataflow graph involving nodes: {cyc}")
        ready_ids = {id(n) for n in ready}
        for n in ready:
            ordered.append(n)
            avail |= set(n.outputs)
        pending = [n for n in pending if id(n) not in ready_ids]
    return ordered


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def execute(g: Graph, values: Dict[str, jnp.ndarray],
            weights: Dict[str, jnp.ndarray], axis: Optional[str] = None,
            cais: CAISConfig = CAISConfig(), norm: str = "rmsnorm",
            backend=None):
    """Evaluate the graph. With ``axis`` set this must run inside shard_map
    (values/weights are local shards per the layout conventions) and every
    fused collective op dispatches through ``backend`` — a
    :class:`repro.core.backends.CollectiveBackend` instance or registry name
    (default ``"cais"``). ``axis`` may be the composite ``("tp_in",
    "tp_out")`` tuple of a hierarchical 2D mesh — fused ops thread it to the
    backend's hierarchical compositions; raw allgather/reduce_scatter nodes
    compose per-tier here (inter-node gather first / intra-node scatter
    first, matching the tp_in-major shard order). Without ``axis``,
    collectives degenerate to identity/plain math (single-device
    reference).

    Each node runs under ``jax.named_scope("df.<op>.<node name>")``, so
    every HLO instruction it emits names its node in ``op_name``
    (``bench/spans.py`` reads device time per node kind from it)."""
    from repro.core.backends import get_backend
    from repro.models.layers import apply_norm

    env = dict(values)
    dist = axis is not None
    hier = isinstance(axis, (tuple, list)) and len(axis) > 1
    be = get_backend(backend if backend is not None else "cais")

    for n in g.nodes:
        if n.op == "input":
            continue
        with jax.named_scope(f"df.{n.op}.{n.name}"):
            ins = [env[v] for v in n.inputs]
            ws = [weights[k] for k in n.weights]
            if n.op == "gemm_col" or n.op == "gemm_row":
                env[n.name] = ins[0] @ ws[0]
            elif n.op == "allgather":
                if dist and hier:
                    out = jax.lax.all_gather(ins[0], axis[-1], axis=1,
                                             tiled=True)
                    env[n.name] = jax.lax.all_gather(out, axis[0], axis=1,
                                                     tiled=True)
                else:
                    env[n.name] = (jax.lax.all_gather(ins[0], axis, axis=1,
                                                      tiled=True)
                                   if dist else ins[0])
            elif n.op == "reduce_scatter":
                if dist and hier:
                    out = jax.lax.psum_scatter(ins[0], axis[0],
                                               scatter_dimension=1, tiled=True)
                    env[n.name] = jax.lax.psum_scatter(out, axis[-1],
                                                       scatter_dimension=1,
                                                       tiled=True)
                else:
                    env[n.name] = (jax.lax.psum_scatter(ins[0], axis,
                                                        scatter_dimension=1,
                                                        tiled=True)
                                   if dist else ins[0])
            elif n.op == "allreduce":
                env[n.name] = jax.lax.psum(ins[0], axis) if dist else ins[0]
            elif n.op == "layernorm":
                env[n.name] = apply_norm(norm, {"scale": ws[0]}, ins[0])
            elif n.op in ("add", "residual"):
                env[n.name] = ins[0] + ins[1]
            elif n.op in _FN_OPS:
                res = n.fn(*ins, *ws)
                if len(n.outputs) > 1:
                    for name, val in zip(n.outputs, res):
                        env[name] = val
                else:
                    env[n.outputs[0]] = res
            elif n.op == "a2a_ffn":
                fn = (lambda chunk, _n=n, _ws=tuple(ws): _n.fn(chunk, *_ws))
                env[n.name] = (be.a2a_expert_ffn(ins[0], fn, axis, cais)
                               if dist else jax.vmap(fn)(ins[0]))
            elif n.op == "ag_gemm":
                env[n.name] = (be.ag_gemm(ins[0], ws[0], axis, cais)
                               if dist else ins[0] @ ws[0])
            elif n.op == "ag_gemm_multi":
                outs = (be.ag_gemm_multi(ins[0], tuple(ws), axis, cais)
                        if dist else tuple(ins[0] @ w for w in ws))
                for name, val in zip(n.outputs, outs):
                    env[name] = val
            elif n.op == "gemm_rs":
                env[n.name] = (be.gemm_rs(ins[0], ws[0], axis, cais)
                               if dist else ins[0] @ ws[0])
            elif n.op == "bwd_ag_gemm":
                # adjoint of gemm_rs: gather the seq-sharded cotangent, GEMM
                # with the transposed weight; the gathered cotangent is
                # re-exposed for the weight-gradient GEMM (outputs (d_x,
                # dy_full))
                dx_, dyf = (be.grad_ag_gemm(ins[0], ws[0], axis, cais)
                            if dist else (ins[0] @ ws[0], ins[0]))
                env[n.outputs[0]], env[n.outputs[1]] = dx_, dyf
            elif n.op == "bwd_a2a_ffn":
                # adjoint of a2a_ffn: re-dispatch (send-row, cotangent-row)
                # pairs to the expert owners, per-row VJP of the expert fn
                # there, return d(recv) to the senders; the owner keeps its
                # local expert-weight grads. outputs = (d_send, dw...)
                def _row_vjp(chunk, gyc, _n=n, _ws=tuple(ws)):
                    _, pull = jax.vjp(lambda c, *w: _n.fn(c, *w), chunk, *_ws)
                    gr = pull(gyc)
                    return gr[0], tuple(gr[1:])
                if dist:
                    dsend, dws_ = be.grad_a2a_expert_ffn(ins[0], ins[1],
                                                         _row_vjp, axis, cais)
                else:
                    d_rows, dw_rows = jax.vmap(_row_vjp)(ins[0], ins[1])
                    dsend = d_rows
                    dws_ = tuple(jnp.sum(a, axis=0) for a in dw_rows)
                for name, val in zip(n.outputs, (dsend,) + tuple(dws_)):
                    env[name] = val
            elif n.op == "gemm_ar":
                env[n.name] = (be.gemm_ar(ins[0], ws[0], axis, cais)
                               if dist else ins[0] @ ws[0])
            elif n.op == "fused_rs_ln_ag":
                w1, scale, w2 = ws
                res = env[n.inputs[1]] if len(n.inputs) > 1 else None
                if dist:
                    out, z = be.fused_rs_ln_ag(ins[0], w1, scale, w2, axis,
                                               cais, norm=norm, residual=res)
                else:
                    z = ins[0] @ w1
                    if res is not None:
                        z = z + res
                    out = apply_norm(norm, {"scale": scale}, z) @ w2
                env[n.outputs[0]], env[n.outputs[1]] = out, z
            elif n.op == "fused_rs_ln_ag_multi":
                w1, scale = ws[0], ws[1]
                ws2 = tuple(ws[2:])
                res = env[n.inputs[1]] if len(n.inputs) > 1 else None
                if dist:
                    outs, z = be.fused_rs_ln_ag_multi(ins[0], w1, scale, ws2,
                                                      axis, cais, norm=norm,
                                                      residual=res)
                else:
                    z = ins[0] @ w1
                    if res is not None:
                        z = z + res
                    zn = apply_norm(norm, {"scale": scale}, z)
                    outs = tuple(zn @ w for w in ws2)
                for name, val in zip(n.outputs, outs + (z,)):
                    env[name] = val
            elif n.op == "fused_rs_ln":
                w1, scale = ws
                res = env[n.inputs[1]] if len(n.inputs) > 1 else None
                if dist:
                    zn, z = be.fused_rs_ln(ins[0], w1, scale, axis, cais,
                                           norm=norm, residual=res)
                else:
                    z = ins[0] @ w1
                    if res is not None:
                        z = z + res
                    zn = apply_norm(norm, {"scale": scale}, z)
                env[n.outputs[0]], env[n.outputs[1]] = zn, z
            elif n.op == "overlap_asym":
                w_rs = ws[0]
                ag_ws = tuple(ws[1:])
                w_ag = ag_ws if len(ag_ws) > 1 else ag_ws[0]
                if dist:
                    rs_out, ag_out = be.overlap_asymmetric(
                        (ins[0], w_rs), (ins[1], w_ag), axis, cais)
                else:
                    rs_out = ins[0] @ w_rs
                    ag_out = (tuple(ins[1] @ w for w in ag_ws)
                              if len(ag_ws) > 1 else ins[1] @ ag_ws[0])
                ag_outs = ag_out if isinstance(ag_out, tuple) else (ag_out,)
                for name, val in zip(n.outputs, (rs_out,) + ag_outs):
                    env[name] = val
            else:
                raise ValueError(n.op)
    return tuple(env[o] for o in g.outputs)


# ---------------------------------------------------------------------------
# Canonical sub-layer graphs (paper Fig. 12, L1–L4)
# ---------------------------------------------------------------------------


def sublayer_graph() -> Graph:
    """[GEMM (row) → RS] → LN → [AG → GEMM (col)] — the L1–L4 shape:
    e.g. L2 = second FFN layer → LayerNorm → input projection."""
    return Graph(
        nodes=[
            Node("x", "input"),
            Node("g1", "gemm_row", ("x",), ("w1",)),
            Node("rs", "reduce_scatter", ("g1",)),
            Node("ln", "layernorm", ("rs",), ("scale",)),
            Node("ag", "allgather", ("ln",)),
            Node("g2", "gemm_col", ("ag",), ("w2",)),
        ],
        outputs=("g2",),
    )


def merge_graphs(graphs: Sequence[Graph],
                 prefixes: Optional[Sequence[str]] = None,
                 share_weights: bool = False) -> Graph:
    """Disjoint union of several graphs with value/node renaming — e.g. two
    microbatches of the same transformer block, or consecutive *different*
    blocks of a period, so cross-graph passes (``pair_asymmetric``) can
    co-schedule collectives across them.

    Weight keys are prefixed exactly like values by default, so merging
    graphs of different blocks cannot silently alias ``wq``/``w_up``/…
    across blocks. Pass ``share_weights=True`` for the same-params
    microbatch case: weight keys are left unrenamed and every merged copy
    reads one shared weights dict. Duplicate prefixes would make the
    renaming collide (unintended weight-key/value aliasing) and raise
    :class:`GraphError` up front."""
    if prefixes is None:
        prefixes = [f"mb{i}." for i in range(len(graphs))]
    if len(prefixes) != len(graphs):
        raise GraphError(
            f"merge_graphs got {len(graphs)} graphs but "
            f"{len(prefixes)} prefixes")
    if len(set(prefixes)) != len(prefixes):
        dup = sorted(p for p in set(prefixes) if list(prefixes).count(p) > 1)
        raise GraphError(
            f"merge_graphs got duplicate prefix {dup[0]!r}: node and weight "
            f"renaming would collide across the merged graphs")
    nodes: List[Node] = []
    outs: List[str] = []
    for g, p in zip(graphs, prefixes):
        for n in g.nodes:
            nodes.append(dataclasses.replace(
                n, name=p + n.name,
                inputs=tuple(p + v for v in n.inputs),
                outputs=tuple(p + v for v in n.outputs),
                weights=(n.weights if share_weights
                         else tuple(p + w for w in n.weights))))
        outs.extend(p + o for o in g.outputs)
    return Graph(nodes, tuple(outs))


def dual_sublayer_graph() -> Graph:
    """Two independent sub-chains (e.g. two microbatches / fwd+bwd): the
    optimizer pairs the RS of one with the AG-GEMM of the other."""
    return Graph(
        nodes=[
            Node("xa", "input"),
            Node("xb", "input"),
            Node("ga", "gemm_row", ("xa",), ("wa",)),
            Node("rsa", "reduce_scatter", ("ga",)),
            Node("agb", "allgather", ("xb",)),
            Node("gb", "gemm_col", ("agb",), ("wb",)),
        ],
        outputs=("rsa", "gb"),
    )


# ---------------------------------------------------------------------------
# Backward: training graphs (declared adjoints per fused forward op)
# ---------------------------------------------------------------------------
#
# build_training_graph takes a forward graph that has been through passes
# 1/1b/2 (NOT pass 3 — overlap_asym has no adjoint; the caller runs pass 3
# on the *merged* result so it can pair forward against backward
# collectives) and appends adjoint nodes in reverse topological order. The
# builder works at the fused-op level on purpose: pass 2 re-exposes every
# activation the adjoints need (z, the normed value is recomputable from z,
# q/k/v/o/h are plain graph values), whereas differentiating the primitive
# graph would hang extra non-gemm consumers off every allgather and block
# passes 1b/2 from fusing the forward at all.
#
# Derived weight keys: adjoints reference transposed (and, for shared
# gathers, concatenated) forward weights as new keys ``"w^T"`` /
# ``"wa+wb^T"``. These are *local-shard* transforms — the transpose of a
# column shard IS that device's shard of the row-sharded transpose — so
# :func:`derived_weights` materializes them inside shard_map from the local
# forward shards, with no extra mesh arguments.

_D_PREFIX = "d."
_DW_PREFIX = "dw."


def grad_input_name(value: str) -> str:
    """Name of the ``input`` node seeding the cotangent of forward output
    ``value`` in a training graph."""
    return _D_PREFIX + value


def supports_backward(g: Graph) -> bool:
    """True iff every node's op has a declared adjoint (:data:`ADJOINTS`) —
    every op the model builders leave in a period graph after passes
    1/1b/2: the dense vocabulary, MoE routing (``route``/``a2a_ffn``/
    ``unroute``), ``gemm_ar``/``gemm_col`` (ragged/decode and
    sequence-parallel-off layouts). Raw collectives and pass-3
    ``overlap_asym`` have none; callers keep JAX autodiff of the executed
    forward graph for those (``sp_period`` warns once when that fallback
    fires under ``graph_backward=True``)."""
    return all(n.op in ADJOINTS for n in g.nodes)


@dataclass(frozen=True)
class TrainingGraph:
    """A forward graph with its graph-built backward appended.

    ``graph.outputs`` = the input cotangents (one per forward ``input``
    node that gradients reach, in forward declaration order) followed by
    every per-use weight-gradient value. ``dweights`` groups the latter by
    forward weight key: shared-weight chains (microbatch copies of one
    block) each contribute one value per use, and the caller sums each
    group (then psums replicated-weight grads over the mesh)."""
    graph: Graph
    grad_inputs: Tuple[str, ...]          # cotangent seeds ("d." + output)
    dx: Dict[str, str]                    # fwd input value -> grad value
    dweights: Dict[str, Tuple[str, ...]]  # weight key -> grad values (sum)


def _norm_vjp(norm: str) -> Callable:
    def vjp_fn(x, gy, scale):
        from repro.models.layers import apply_norm
        _, pull = jax.vjp(
            lambda xx, ss: apply_norm(norm, {"scale": ss}, xx), x, scale)
        return pull(gy)          # (d_x, d_scale)
    return vjp_fn


def _norm_fwd(norm: str) -> Callable:
    def fwd_fn(x, scale):
        from repro.models.layers import apply_norm
        return apply_norm(norm, {"scale": scale}, x)
    return fwd_fn


def _fn_vjp(fn: Callable, k_in: int, k_w: int,
            mask: Tuple[bool, ...]) -> Callable:
    """Adjoint of a ``custom`` node's fn. Called as
    ``vjp(*fwd_inputs, *present_cotangents, *fwd_weights)`` (the executor's
    ``fn(*ins, *ws)`` convention); absent cotangents (outputs no gradient
    reaches) are zero-filled against the recomputed primals."""
    def vjp_fn(*args):
        prim = args[:k_in]
        cots = args[k_in:len(args) - k_w] if k_w else args[k_in:]
        ws = args[len(args) - k_w:] if k_w else ()
        outs, pull = jax.vjp(fn, *prim, *ws)
        it = iter(cots)
        if len(mask) == 1:
            cot = next(it)
        else:
            cot = tuple(next(it) if m else jnp.zeros_like(o)
                        for m, o in zip(mask, outs))
        grads = pull(cot)        # cotangents for (inputs..., weights...)
        return grads if len(grads) > 1 else grads[0]
    return vjp_fn


def _concat_last(*gs):
    return jnp.concatenate(gs, axis=-1)


def _dw(act, gout):
    """Per-use weight gradient: contract activation (B, S, in) against the
    output cotangent (B, S, out) over batch×seq → (in, out)."""
    return jnp.einsum("bsi,bsj->ij", act, gout)


def _gemm_t(gy, wT):
    """dx leg of a plain GEMM adjoint: cotangent @ transposed weight."""
    return gy @ wT


def build_training_graph(g: Graph, norm: str = "rmsnorm") -> TrainingGraph:
    """Append the graph-built backward to forward graph ``g`` (which must be
    post-pass-1/1b/2 and pre-pass-3; see the section comment above).

    Every forward output gets a cotangent seed ``input`` node
    (:func:`grad_input_name`); adjoints are emitted per the declared
    :data:`ADJOINTS` vocabulary in reverse topo order, accumulating fan-out
    gradients through ``add`` nodes. The result is ONE graph containing
    both directions — run :func:`optimize` on it so pass 3 can pair a
    backward grad reduce-scatter against an independent chain's forward
    gather (the fwd/bwd cross-chain ``overlap_asym`` the paper targets)."""
    bad = sorted({n.op for n in g.nodes if n.op not in ADJOINTS})
    if bad:
        raise GraphError(
            f"no declared adjoint for op {bad[0]!r}; gate on "
            f"supports_backward() and fall back to JAX autodiff")
    fwd = _topo(list(g.nodes), g.outputs)
    nodes: List[Node] = list(fwd)
    contrib: Dict[str, List[str]] = {}
    dweights: Dict[str, List[str]] = {}
    grad_inputs = tuple(grad_input_name(o) for o in g.outputs)
    for o, gi in zip(g.outputs, grad_inputs):
        nodes.append(Node(gi, "input"))
        contrib.setdefault(o, []).append(gi)

    def finalize(v: str) -> Optional[str]:
        # sum the contributions to d(v); None if no gradient reaches v
        parts = contrib.get(v)
        if not parts:
            return None
        acc = parts[0]
        for i, p in enumerate(parts[1:]):
            nm = f"dsum{i}.{v}"
            nodes.append(Node(nm, "add", (acc, p)))
            acc = nm
        return acc

    def take(v: str, grad: str) -> None:
        contrib.setdefault(v, []).append(grad)

    def add_dw(w: str, grad: str) -> None:
        dweights.setdefault(w, []).append(grad)

    def grad_rs(n: Node, gys: List[str], an: str, xn: str) -> str:
        # shared d(gathered-input) leg of ag_gemm[_multi] and the fused ops:
        # concat the per-weight cotangents and reduce-scatter them through
        # the transposed (concatenated) weight — the grad reduce-scatter
        if len(gys) > 1:
            cat = f"dcat.{n.name}"
            nodes.append(Node(f"adj.cat.{n.name}", "custom", tuple(gys),
                              outputs=(cat,), fn=_concat_last))
        else:
            cat = gys[0]
        out = f"d.{xn}@{an}"
        nodes.append(Node(out, "gemm_rs", (cat,),
                          ("+".join(n.weights[-len(gys):]) + "^T",)))
        return out

    dx: Dict[str, str] = {}
    for n in reversed(fwd):
        an = f"adj.{n.name}"
        if n.op == "input":
            dxv = finalize(n.name)
            if dxv is not None:
                dx[n.name] = dxv
        elif n.op in ("add", "residual"):
            gy = finalize(n.name)
            if gy is not None:
                for v in n.inputs:
                    take(v, gy)
        elif n.op == "layernorm":
            gy = finalize(n.name)
            if gy is None:
                continue
            xin, scale = n.inputs[0], n.weights[0]
            nodes.append(Node(
                an, "custom", (xin, gy), (scale,),
                outputs=(f"d.{xin}@{an}", f"{_DW_PREFIX}{an}.{scale}"),
                fn=_norm_vjp(norm)))
            take(xin, f"d.{xin}@{an}")
            add_dw(scale, f"{_DW_PREFIX}{an}.{scale}")
        elif n.op in _FN_OPS:
            # custom, route, unroute: jax.vjp of the node's local fn. For
            # route the output triple is (send, combine, aux) — the aux
            # load-balance statistic is a first-class graph output, so its
            # cotangent (seeded from d.<aux>) rides the same VJP into the
            # router-logit gradients.
            gys = [finalize(v) for v in n.outputs]
            if all(q is None for q in gys):
                continue
            have = tuple(q for q in gys if q is not None)
            mask = tuple(q is not None for q in gys)
            outs = (tuple(f"d.{v}@{an}" for v in n.inputs)
                    + tuple(f"{_DW_PREFIX}{an}.{w}" for w in n.weights))
            nodes.append(Node(
                an, "custom", n.inputs + have, n.weights, outputs=outs,
                fn=_fn_vjp(n.fn, len(n.inputs), len(n.weights), mask)))
            for v in n.inputs:
                take(v, f"d.{v}@{an}")
            for w in n.weights:
                add_dw(w, f"{_DW_PREFIX}{an}.{w}")
        elif n.op == "a2a_ffn":
            gy = finalize(n.name)
            if gy is None:
                continue
            sn = n.inputs[0]
            dsend = f"d.{sn}@{an}"
            dw_names = tuple(f"{_DW_PREFIX}{an}.{w}" for w in n.weights)
            nodes.append(Node(an, "bwd_a2a_ffn", (sn, gy), n.weights,
                              outputs=(dsend,) + dw_names, fn=n.fn))
            take(sn, dsend)
            for w, dwn in zip(n.weights, dw_names):
                add_dw(w, dwn)
        elif n.op in ("ag_gemm", "ag_gemm_multi"):
            gys = [finalize(v) for v in n.outputs]
            if all(q is None for q in gys):
                continue
            if any(q is None for q in gys):
                raise GraphError(
                    f"partial cotangents for shared gather {n.name!r}: "
                    f"every output of an ag_gemm_multi must be consumed")
            xn = n.inputs[0]
            take(xn, grad_rs(n, gys, an, xn))
            # weight grads re-gather the seq-sharded input (Megatron-style
            # recompute of the gathered activation — a raw IR allgather so
            # the planner sees and costs it)
            xg = f"xg.{n.name}"
            nodes.append(Node(xg, "allgather", (xn,)))
            for w, gy in zip(n.weights, gys):
                nodes.append(Node(f"adj.dw.{n.name}.{w}", "custom",
                                  (xg, gy),
                                  outputs=(f"{_DW_PREFIX}{an}.{w}",),
                                  fn=_dw))
                add_dw(w, f"{_DW_PREFIX}{an}.{w}")
        elif n.op == "gemm_rs":
            gy = finalize(n.name)
            if gy is None:
                continue
            hin, w1 = n.inputs[0], n.weights[0]
            dh, dyf = f"d.{hin}@{an}", f"dfull.{n.name}"
            nodes.append(Node(an, "bwd_ag_gemm", (gy,), (w1 + "^T",),
                              outputs=(dh, dyf)))
            take(hin, dh)
            nodes.append(Node(f"adj.dw.{n.name}.{w1}", "custom",
                              (hin, dyf),
                              outputs=(f"{_DW_PREFIX}{an}.{w1}",), fn=_dw))
            add_dw(w1, f"{_DW_PREFIX}{an}.{w1}")
        elif n.op == "gemm_ar":
            # y = psum(x_feat @ w_row) is replicated, so the adjoint is
            # purely local: dx = dy @ w^T lands feature-sharded, dw is the
            # local row-shard's contraction — no collective either way
            # (decode/ragged S, incl. S=1: nothing here depends on S).
            gy = finalize(n.name)
            if gy is None:
                continue
            hin, w1 = n.inputs[0], n.weights[0]
            dh = f"d.{hin}@{an}"
            nodes.append(Node(an, "custom", (gy,), (w1 + "^T",),
                              outputs=(dh,), fn=_gemm_t))
            take(hin, dh)
            nodes.append(Node(f"adj.dw.{n.name}.{w1}", "custom",
                              (hin, gy),
                              outputs=(f"{_DW_PREFIX}{an}.{w1}",), fn=_dw))
            add_dw(w1, f"{_DW_PREFIX}{an}.{w1}")
        elif n.op == "gemm_col":
            # sequence-parallel-off layout: x is replicated, y = x @ w_col
            # is feature-sharded. dx needs the cross-shard sum — emitted as
            # a backward ``gemm_ar`` (grad allreduce through w^T, dispatched
            # via the backend); dw is local per column shard.
            gy = finalize(n.name)
            if gy is None:
                continue
            xin, w1 = n.inputs[0], n.weights[0]
            dxv = f"d.{xin}@{an}"
            nodes.append(Node(dxv, "gemm_ar", (gy,), (w1 + "^T",)))
            take(xin, dxv)
            nodes.append(Node(f"adj.dw.{n.name}.{w1}", "custom",
                              (xin, gy),
                              outputs=(f"{_DW_PREFIX}{an}.{w1}",), fn=_dw))
            add_dw(w1, f"{_DW_PREFIX}{an}.{w1}")
        elif n.op in ("fused_rs_ln_ag", "fused_rs_ln_ag_multi"):
            gs, z = n.outputs[:-1], n.outputs[-1]
            gys = [finalize(v) for v in gs]
            dz_ext = finalize(z)
            if all(q is None for q in gys) and dz_ext is None:
                continue
            if any(q is None for q in gys):
                raise GraphError(
                    f"partial cotangents for fused seam {n.name!r}: every "
                    f"gather output must be consumed")
            hin = n.inputs[0]
            res = n.inputs[1] if len(n.inputs) > 1 else None
            w1, scale = n.weights[0], n.weights[1]
            # d(zn): the grad reduce-scatter through the w2 leg
            dzn = grad_rs(n, gys, an, f"zn.{n.name}")
            # norm VJP: d(z) from d(zn) (needs z, re-exposed by pass 2)
            dz_n, dscale = f"dznorm.{n.name}", f"{_DW_PREFIX}{an}.{scale}"
            nodes.append(Node(f"adj.ln.{n.name}", "custom", (z, dzn),
                              (scale,), outputs=(dz_n, dscale),
                              fn=_norm_vjp(norm)))
            add_dw(scale, dscale)
            if dz_ext is not None:
                dz = f"dz.{n.name}"
                nodes.append(Node(dz, "add", (dz_n, dz_ext)))
            else:
                dz = dz_n
            if res is not None:
                take(res, dz)
            # grad all-gather back through the RS leg
            dh, dyf = f"d.{hin}@{an}", f"dfull.{n.name}"
            nodes.append(Node(an, "bwd_ag_gemm", (dz,), (w1 + "^T",),
                              outputs=(dh, dyf)))
            take(hin, dh)
            nodes.append(Node(f"adj.dw.{n.name}.{w1}", "custom",
                              (hin, dyf),
                              outputs=(f"{_DW_PREFIX}{an}.{w1}",), fn=_dw))
            add_dw(w1, f"{_DW_PREFIX}{an}.{w1}")
            # w2 grads: recompute zn from the re-exposed z, re-gather it
            znr, zg = f"znr.{n.name}", f"zg.{n.name}"
            nodes.append(Node(znr, "custom", (z,), (scale,),
                              fn=_norm_fwd(norm)))
            nodes.append(Node(zg, "allgather", (znr,)))
            for w, gy in zip(n.weights[2:], gys):
                nodes.append(Node(f"adj.dw.{n.name}.{w}", "custom",
                                  (zg, gy),
                                  outputs=(f"{_DW_PREFIX}{an}.{w}",),
                                  fn=_dw))
                add_dw(w, f"{_DW_PREFIX}{an}.{w}")
        elif n.op == "fused_rs_ln":
            # the MoE router seam (no trailing gather): outputs (zn, z).
            # d(zn) arrives from the route/unroute/dense-residual adjoints,
            # d(z) from the next block's residual skip; norm VJP joins them
            # and the RS leg's adjoint (bwd_ag_gemm) carries dz back.
            znv, z = n.outputs
            dzn = finalize(znv)
            dz_ext = finalize(z)
            if dzn is None and dz_ext is None:
                continue
            hin = n.inputs[0]
            res = n.inputs[1] if len(n.inputs) > 1 else None
            w1, scale = n.weights[0], n.weights[1]
            if dzn is not None:
                dz_n = f"dznorm.{n.name}"
                dscale = f"{_DW_PREFIX}{an}.{scale}"
                nodes.append(Node(f"adj.ln.{n.name}", "custom", (z, dzn),
                                  (scale,), outputs=(dz_n, dscale),
                                  fn=_norm_vjp(norm)))
                add_dw(scale, dscale)
                if dz_ext is not None:
                    dz = f"dz.{n.name}"
                    nodes.append(Node(dz, "add", (dz_n, dz_ext)))
                else:
                    dz = dz_n
            else:
                dz = dz_ext
            if res is not None:
                take(res, dz)
            dh, dyf = f"d.{hin}@{an}", f"dfull.{n.name}"
            nodes.append(Node(an, "bwd_ag_gemm", (dz,), (w1 + "^T",),
                              outputs=(dh, dyf)))
            take(hin, dh)
            nodes.append(Node(f"adj.dw.{n.name}.{w1}", "custom",
                              (hin, dyf),
                              outputs=(f"{_DW_PREFIX}{an}.{w1}",), fn=_dw))
            add_dw(w1, f"{_DW_PREFIX}{an}.{w1}")
        else:  # pragma: no cover — ADJOINTS gate above is exhaustive
            raise GraphError(f"unhandled adjoint for op {n.op!r}")

    fwd_inputs = [n.name for n in fwd if n.op == "input"]
    dx_outs = tuple(dx[v] for v in fwd_inputs if v in dx)
    dw_outs = tuple(v for vals in dweights.values() for v in vals)
    tg = Graph(nodes, dx_outs + dw_outs).validate()
    return TrainingGraph(tg, grad_inputs, dx,
                         {k: tuple(v) for k, v in dweights.items()})


def derived_weight_keys(g: Graph) -> List[str]:
    """The transposed/concatenated weight keys (suffix ``"^T"``) a training
    graph references beyond the forward weights, in first-use order."""
    seen, out = set(), []
    for n in g.nodes:
        for w in n.weights:
            if w.endswith("^T") and w not in seen:
                seen.add(w)
                out.append(w)
    return out


def derived_weights(g: Graph, weights: Dict) -> Dict:
    """Extend ``weights`` with the derived keys of ``g``: ``"w^T"`` is the
    (local-shard) transpose of ``weights["w"]``; ``"a+b^T"`` concatenates
    the named shards along their last axis first (the shared-gather layout)
    then transposes. Local transforms only — valid inside shard_map."""
    out = dict(weights)
    for key in derived_weight_keys(g):
        parts = key[:-2].split("+")
        w = (out[parts[0]] if len(parts) == 1 else
             jnp.concatenate([out[p] for p in parts], axis=-1))
        out[key] = w.T if hasattr(w, "T") else w
    return out


def derived_weight_shapes(g: Graph, shapes: Dict) -> Dict:
    """Shape-level twin of :func:`derived_weights` for the planner: maps the
    derived keys to (out, in)-transposed / concat-then-transposed shapes."""
    out = dict(shapes)
    for key in derived_weight_keys(g):
        parts = key[:-2].split("+")
        d = out[parts[0]][0]
        f = sum(out[p][-1] for p in parts)
        out[key] = (f, d)
    return out
