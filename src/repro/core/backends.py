"""CollectiveBackend: the registry-dispatched TP execution API.

Every tensor-parallel collective-fused schedule the model path can run —
AG→GEMM, GEMM→RS, GEMM→AR, the expert all-to-all, the fused RS+LN+AG
sub-layer chain (and its gather-less RS+LN prefix for the MoE router seam),
and the asymmetric dual-stream overlap — is reached through
one seam: a :class:`CollectiveBackend` instance looked up by name in a
process-global registry. ``repro.core.tp`` and ``repro.core.dataflow.execute``
dispatch through the backend instead of branching on mode strings, so adding
a new communication strategy is *one registration*, not an edit of every
sub-layer.

Built-in backends
-----------------
``barrier``
    The NVLS-style communication-centric baseline: one monolithic collective
    HLO op (all-gather / reduce-scatter / all-to-all) around each GEMM.
``cais``
    The paper's compute-aware decomposed schedules
    (:mod:`repro.core.primitives`): ring ``collective_permute`` chains
    interleaved with partial GEMMs. When ``CAISConfig.num_chunks`` is None
    the backend is *compute-aware in the paper's §III-B sense*: it picks the
    chunking per collective from the payload bytes and ring size via
    :func:`repro.core.coordination.plan` (cached per shape); an explicit
    integer in the config is honored as a static override.
``auto``
    Reference backend that defers scheduling to XLA. Its methods are the
    plain monolithic formulations (identical math to ``barrier``), and it
    reports ``explicit = False``: the model path skips ``shard_map`` entirely
    and lets the compiler place collectives from sharding constraints.

Registration API
----------------
::

    from repro.core.backends import CollectiveBackend, register_backend

    class MyBackend(CollectiveBackend):
        name = "mine"
        def ag_gemm_multi(self, x, ws, axis, cais): ...
        ...

    register_backend(MyBackend())   # now TPConfig(mode="mine") works
    get_backend("mine")                    # -> the instance
    available_backends()                   # -> ["auto", "barrier", "cais", "mine"]

All methods run INSIDE ``shard_map`` (they may use ``lax.axis_index`` /
``lax.ppermute``); ``repro.core.tp`` owns the pjit-callable wrapping.

``docs/backends.md`` is the authoring guide: which methods are mandatory vs
composed by default from the backend's own primitives (``gemm_ar``,
``fused_rs_ln``, ``fused_rs_ln_ag[_multi]``), with ``barrier``/``cais`` as
the worked examples.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import coordination
from repro.core import primitives as prim
from repro.core.primitives import CAISConfig


class CollectiveBackend:
    """Protocol for TP collective-fused execution strategies.

    Subclasses implement the seven schedule methods below; ``name`` is the
    registry key and ``explicit`` says whether the model path should enter
    ``shard_map`` for this backend (False = XLA-scheduled reference).
    Method contracts match :mod:`repro.core.primitives` (same shapes and
    layouts; see the module docstring there).
    """

    name: str = "abstract"
    explicit: bool = True

    # -- AG-aligned -------------------------------------------------------
    def ag_gemm(self, x, w, axis: str, cais: CAISConfig) -> jnp.ndarray:
        """(B, S_loc, d) seq-sharded x; (d, F_loc) w -> (B, S, F_loc)."""
        return self.ag_gemm_multi(x, (w,), axis, cais)[0]

    def ag_gemm_multi(self, x, ws: Sequence, axis: str,
                      cais: CAISConfig) -> Tuple[jnp.ndarray, ...]:
        """One gather shared by several column-sharded weights (QKV, up+gate)."""
        raise NotImplementedError

    # -- RS/AR-aligned ----------------------------------------------------
    def gemm_rs(self, x, w, axis: str, cais: CAISConfig) -> jnp.ndarray:
        """(B, S, d_loc) feat-sharded x; (d_loc, F) w -> (B, S_loc, F)."""
        raise NotImplementedError

    def gemm_ar(self, x, w, axis: str, cais: CAISConfig) -> jnp.ndarray:
        """(B, S, d_loc) feat-sharded x; (d_loc, F) w -> (B, S, F) reduced.
        Default: AR = RS + AG composed from the backend's own ``gemm_rs``
        (the decode/ragged-S dense schedule works on any backend that
        implements the RS side). Falls back to a monolithic allreduce when
        the sequence cannot scatter over the ring (S % n != 0, e.g. S=1)."""
        if self.hierarchical(axis):
            return self.hier_gemm_ar(x, w, axis, cais)
        n = jax.lax.axis_size(axis) if cais.interpret_n is None \
            else cais.interpret_n
        if int(x.shape[1]) % max(n, 1) != 0:
            return prim.barrier_gemm_ar(x, w, axis)
        y = self.gemm_rs(x, w, axis, cais)
        return lax.all_gather(y, axis, axis=1, tiled=True)

    # -- EP ---------------------------------------------------------------
    def a2a_expert_ffn(self, send, ffn: Callable, axis: str,
                       cais: CAISConfig) -> jnp.ndarray:
        """(n, C, d) routed chunks -> (n, C, d) expert outputs (see prim)."""
        raise NotImplementedError

    # -- fused sub-layer chain -------------------------------------------
    def fused_rs_ln_ag(self, x, w1, ln_scale, w2, axis: str, cais: CAISConfig,
                       norm: str = "rmsnorm", residual=None):
        """GEMM-RS -> (+res) -> LN -> AG-GEMM. Returns (out, z). Default:
        composed from the backend's own ``gemm_rs`` / ``ag_gemm``, so custom
        backends get the fused seam for free (non-gated blocks fuse to this
        single-weight form)."""
        outs, z = self.fused_rs_ln_ag_multi(x, w1, ln_scale, (w2,), axis,
                                            cais, norm=norm,
                                            residual=residual)
        return outs[0], z

    def fused_rs_ln_ag_multi(self, x, w1, ln_scale, ws2: Sequence, axis: str,
                             cais: CAISConfig, norm: str = "rmsnorm",
                             residual=None):
        """GEMM-RS -> (+res) -> LN -> shared-gather AG-GEMM against several
        weights (the whole-block attention-out → gated-FFN-in seam).
        Returns (per-weight outputs tuple, z). Default: composed from the
        backend's own ``gemm_rs`` / ``ag_gemm_multi``, so custom backends
        get the fused seam for free."""
        from repro.models.layers import apply_norm

        z = self.gemm_rs(x, w1, axis, cais)
        if residual is not None:
            z = z + residual
        zn = apply_norm(norm, {"scale": ln_scale}, z)
        return self.ag_gemm_multi(zn, tuple(ws2), axis, cais), z

    def fused_rs_ln(self, x, w1, ln_scale, axis: str, cais: CAISConfig,
                    norm: str = "rmsnorm", residual=None):
        """GEMM-RS -> (+res) -> LN with no trailing gather — the MoE
        attention-out → router seam (the next collective is the expert
        all-to-all). Returns (normed, z). Default: composed from the
        backend's own ``gemm_rs``, so custom backends get it for free."""
        from repro.models.layers import apply_norm

        z = self.gemm_rs(x, w1, axis, cais)
        if residual is not None:
            z = z + residual
        return apply_norm(norm, {"scale": ln_scale}, z), z

    # -- backward collectives (training graphs, docs/training.md) ---------
    def grad_ag_gemm(self, d, wT, axis: str, cais: CAISConfig):
        """Adjoint of ``gemm_rs`` (the ``bwd_ag_gemm`` IR op): all-gather the
        seq-sharded output cotangent ``d`` (B, S_loc, F) and GEMM it with the
        transposed local weight shard ``wT`` (F, d_loc). Returns
        ``(d @ wT gathered, d gathered)`` — the second output feeds the
        weight-gradient GEMM, so the gather runs once. Default: one
        monolithic all-gather (the barrier schedule)."""
        if self.hierarchical(axis):
            return self.hier_grad_ag_gemm(d, wT, axis, cais)
        g = lax.all_gather(d, axis, axis=1, tiled=True)
        return g @ wT, g

    def grad_a2a_expert_ffn(self, send, gy, bwd_row: Callable, axis: str,
                            cais: CAISConfig):
        """Adjoint of ``a2a_expert_ffn`` (the ``bwd_a2a_ffn`` IR op):
        re-run the dispatch all-to-all for ``send`` AND for the output
        cotangent ``gy`` (Megatron-style recompute — the forward's routed
        chunks are not stashed), apply the per-row expert VJP
        ``bwd_row(chunk, gy_row) -> (d_chunk, dw_tuple)`` at the owning
        device, then reverse-a2a the chunk cotangents back to their
        senders. Expert weight grads stay LOCAL at the owner (summed over
        the arriving rows) — EP weight gradients never ride a collective.
        Default: monolithic all-to-alls (the barrier schedule)."""
        if self.hierarchical(axis):
            return self.hier_grad_a2a_expert_ffn(send, gy, bwd_row, axis,
                                                 cais)
        n = jax.lax.axis_size(axis) if cais.interpret_n is None \
            else cais.interpret_n
        if n == 1:
            d_rows, dw_rows = jax.vmap(bwd_row)(send, gy)
            return d_rows, tuple(jnp.sum(a, axis=0) for a in dw_rows)

        def a2a(t):
            return lax.all_to_all(t, axis, split_axis=0, concat_axis=0,
                                  tiled=False)

        recv = a2a(send)
        gyr = a2a(gy)
        d_rows, dw_rows = jax.vmap(bwd_row)(recv, gyr)
        return a2a(d_rows), tuple(jnp.sum(a, axis=0) for a in dw_rows)

    # -- asymmetric dual-stream overlap ----------------------------------
    def overlap_asymmetric(self, rs_args, ag_args, axis: str,
                           cais: CAISConfig):
        """Independent GEMM-RS + AG-GEMM pair; the AG side's weight may be a
        tuple (paired ``ag_gemm_multi``). Returns (rs_out, ag_out[s])."""
        raise NotImplementedError

    # -- hierarchical (2D-mesh) compositions ------------------------------
    # ``axis`` may be the composite ``("tp_in", "tp_out")`` tuple from
    # ``sharding.tp_axes`` (tp_in MAJOR in the flattened shard order, so the
    # slow axis's shard index is minor). Concrete methods dispatch here for
    # tuple axes; the compositions run the inter-node legs through the
    # ``_outer_*`` hooks (monolithic by default, ring-decomposed with
    # inter-tier chunk planning on cais) and reuse the backend's OWN fused
    # schedules on the fast intra-node ring — custom backends become
    # 2D-capable without new code. docs/topology.md derives the orderings:
    # AG gathers inter-node first (minor index → contiguous intra blocks),
    # RS scatters intra-node first.

    @staticmethod
    def hierarchical(axis) -> bool:
        """True when ``axis`` is a composite (2D-mesh) axis tuple."""
        return isinstance(axis, (tuple, list)) and len(axis) > 1

    def _inner_all_gather(self, x, axis: str, cais: CAISConfig):
        """Intra-node all-gather leg (dim 1) of hierarchical AR."""
        return lax.all_gather(x, axis, axis=1, tiled=True)

    def _outer_all_gather(self, x, axis: str, cais: CAISConfig):
        """Inter-node all-gather leg (dim 1)."""
        return lax.all_gather(x, axis, axis=1, tiled=True)

    def _outer_reduce_scatter(self, x, axis: str, cais: CAISConfig):
        """Inter-node reduce-scatter leg (dim 1)."""
        return lax.psum_scatter(x, axis, scatter_dimension=1, tiled=True)

    def hier_ag_gemm_multi(self, x, ws, axis, cais: CAISConfig):
        """AG→GEMM on the composite axis: gather the slow inter-node axis
        first (its shard index is minor, so the concat yields this node's
        contiguous block), then the backend's fused schedule on tp_in."""
        xg = self._outer_all_gather(x, axis[-1], cais)
        return self.ag_gemm_multi(xg, tuple(ws), axis[0], cais)

    def hier_gemm_rs(self, x, w, axis, cais: CAISConfig):
        """GEMM→RS on the composite axis: the backend's fused intra-node
        reduce-scatter first (tp_in-major shard order), then the inter-node
        exchange on 1/tp_in of the payload."""
        y = self.gemm_rs(x, w, axis[0], cais)
        return self._outer_reduce_scatter(y, axis[-1], cais)

    def hier_gemm_ar(self, x, w, axis, cais: CAISConfig):
        """GEMM→AR: intra-node reduce-scatter → inter-node exchange →
        all-gather back out through both tiers (the classic hierarchical
        AR). Ragged sequences that cannot scatter over the full composite
        ring fall back to the monolithic allreduce — ``lax.psum`` takes the
        axis tuple directly."""
        axes = tuple(axis)
        if int(x.shape[1]) % max(jax.lax.axis_size(axes), 1) != 0:
            return prim.barrier_gemm_ar(x, w, axes)
        y = self.hier_gemm_rs(x, w, axis, cais)
        y = self._outer_all_gather(y, axis[-1], cais)
        return self._inner_all_gather(y, axis[0], cais)

    def hier_a2a_expert_ffn(self, send, ffn: Callable, axis,
                            cais: CAISConfig):
        """Grouped-EP expert all-to-all: experts replicate across ``tp_in``
        and shard over ``tp_out`` only, so the dispatch/combine traffic
        never crosses the intra-node ring (``send`` is (tp_out, C, d))."""
        return self.a2a_expert_ffn(send, ffn, axis[-1], cais)

    def hier_grad_ag_gemm(self, d, wT, axis, cais: CAISConfig):
        """Adjoint gather through both tiers: inter-node first, intra-node
        second (same ordering as the forward hierarchical AG)."""
        g = self._outer_all_gather(d, axis[-1], cais)
        g = self._inner_all_gather(g, axis[0], cais)
        return g @ wT, g

    def hier_grad_a2a_expert_ffn(self, send, gy, bwd_row, axis,
                                 cais: CAISConfig):
        """Grouped-EP adjoint: exactly like the forward, the grad
        dispatch/combine traffic runs on ``tp_out`` only — grouped-EP
        gradients never cross the fast intra-node ring (experts replicate
        across ``tp_in``; the per-owner dw sums are completed by the
        training wrapper's weight-grad psum over ``tp_in``)."""
        return self.grad_a2a_expert_ffn(send, gy, bwd_row, axis[-1], cais)

    def hier_overlap_asymmetric(self, rs_args, ag_args, axis,
                                cais: CAISConfig):
        """The lockstep dual-stream schedule is a single-ring construct; on
        2D meshes the two sides run as their hierarchical compositions (the
        intra-node legs still overlap under the compiler's scheduler; the
        inter-node legs serialize)."""
        x_rs, w_rs = rs_args
        x_ag, w_ag = ag_args
        rs_out = self.gemm_rs(x_rs, w_rs, axis, cais)
        multi = isinstance(w_ag, (tuple, list))
        ag_out = self.ag_gemm_multi(x_ag,
                                    tuple(w_ag) if multi else (w_ag,),
                                    axis, cais)
        return rs_out, (ag_out if multi else ag_out[0])


# ---------------------------------------------------------------------------
# barrier — monolithic NVLS-style collectives around each GEMM
# ---------------------------------------------------------------------------


class BarrierBackend(CollectiveBackend):
    """Communication-centric baseline: opaque collective phases. On 2D
    meshes the AG/RS sides compose hierarchically from monolithic per-axis
    legs; ``gemm_ar`` stays ONE opaque allreduce (``lax.psum`` accepts the
    composite axis tuple) — the baseline's defining phase structure."""

    name = "barrier"

    def ag_gemm_multi(self, x, ws, axis, cais):
        if self.hierarchical(axis):
            return self.hier_ag_gemm_multi(x, ws, axis, cais)
        xg = lax.all_gather(x, axis, axis=1, tiled=True)
        return tuple(xg @ w for w in ws)

    def gemm_rs(self, x, w, axis, cais):
        if self.hierarchical(axis):
            return self.hier_gemm_rs(x, w, axis, cais)
        return prim.barrier_gemm_rs(x, w, axis)

    def gemm_ar(self, x, w, axis, cais):
        return prim.barrier_gemm_ar(
            x, w, tuple(axis) if self.hierarchical(axis) else axis)

    def a2a_expert_ffn(self, send, ffn, axis, cais):
        if self.hierarchical(axis):
            return self.hier_a2a_expert_ffn(send, ffn, axis, cais)
        return prim.barrier_a2a_expert_ffn(send, ffn, axis)

    def overlap_asymmetric(self, rs_args, ag_args, axis, cais):
        if self.hierarchical(axis):
            return self.hier_overlap_asymmetric(rs_args, ag_args, axis, cais)
        x_rs, w_rs = rs_args
        x_ag, w_ag = ag_args
        rs_out = prim.barrier_gemm_rs(x_rs, w_rs, axis)
        if isinstance(w_ag, (tuple, list)):
            return rs_out, self.ag_gemm_multi(x_ag, tuple(w_ag), axis, cais)
        return rs_out, prim.barrier_ag_gemm(x_ag, w_ag, axis)


# ---------------------------------------------------------------------------
# cais — decomposed ring schedules with compute-aware chunk planning
# ---------------------------------------------------------------------------


@lru_cache(maxsize=512)
def _planned_chunks(payload_bytes: int, ring: int, bidirectional: bool,
                    hw=None) -> int:
    """coordination.plan() keyed per (payload, ring, hw) — shapes are static
    under jit so the cache collapses repeated traces to one planner call.
    ``hw`` is the α-β tier being planned (None → V5E); hierarchical legs
    pass the inter-node tier here so the slow axis is never planned against
    the intra-node bandwidth."""
    return coordination.plan(float(payload_bytes), ring,
                             bidirectional=bidirectional,
                             hw=hw or coordination.V5E).num_chunks


class CAISBackend(CollectiveBackend):
    """The paper's technique: permute chains interleaved with partial GEMMs,
    chunked per-collective by the coordination planner unless the caller
    pins ``CAISConfig.num_chunks``."""

    name = "cais"

    @staticmethod
    def plan_chunks(payload_bytes: float, ring: int,
                    bidirectional: bool = True, hw=None) -> int:
        """The chunking the backend would auto-pick for this collective
        (``hw=None`` → V5E; pass ``hw.inter_tier()`` for inter-node legs)."""
        return _planned_chunks(int(payload_bytes), ring, bidirectional, hw)

    def _ring(self, axis, cais: CAISConfig) -> int:
        return cais.interpret_n or jax.lax.axis_size(axis)

    def _resolve(self, cais: CAISConfig, gathered_bytes: float,
                 ring: int, inter: bool = False) -> CAISConfig:
        """Fill in num_chunks from the α-β plan when the config leaves it
        open. ``gathered_bytes`` is the full (global) payload the collective
        moves around the ring; ``inter=True`` plans the leg against the
        inter-node tier of ``cais.hw`` (the 2D-mesh slow axis)."""
        if cais.num_chunks is not None or ring <= 1:
            return cais
        hw = cais.hw
        if inter:
            hw = (hw or coordination.V5E).inter_tier()
        c = _planned_chunks(int(gathered_bytes), ring, cais.bidirectional, hw)
        return dataclasses.replace(cais, num_chunks=c)

    @staticmethod
    def _nbytes(x) -> int:
        return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize

    # inter-node legs of the hierarchical compositions: ring-decomposed,
    # chunk-planned against the inter-node tier of ``cais.hw``
    def _inner_all_gather(self, x, axis, cais):
        return prim.ring_all_gather(x, axis, cais)

    def _outer_all_gather(self, x, axis, cais):
        ring = jax.lax.axis_size(axis)
        if ring <= 1:
            return x
        cais = self._resolve(cais, self._nbytes(x) * ring, ring, inter=True)
        return prim.ring_all_gather(x, axis, cais)

    def _outer_reduce_scatter(self, x, axis, cais):
        ring = jax.lax.axis_size(axis)
        if ring <= 1:
            return x
        if int(x.shape[1]) % ring != 0:
            return lax.psum_scatter(x, axis, scatter_dimension=1, tiled=True)
        cais = self._resolve(cais, self._nbytes(x), ring, inter=True)
        return prim.ring_reduce_scatter(x, axis, cais)

    def ag_gemm_multi(self, x, ws, axis, cais):
        if self.hierarchical(axis):
            return self.hier_ag_gemm_multi(x, ws, axis, cais)
        n = self._ring(axis, cais)
        cais = self._resolve(cais, self._nbytes(x) * n, n)
        return prim.ag_gemm_multi(x, tuple(ws), axis, cais)

    def gemm_rs(self, x, w, axis, cais):
        if self.hierarchical(axis):
            return self.hier_gemm_rs(x, w, axis, cais)
        return prim.gemm_rs(x, w, axis, cais)

    def gemm_ar(self, x, w, axis, cais):
        if self.hierarchical(axis):
            return self.hier_gemm_ar(x, w, axis, cais)
        # the decomposed RS+AG schedule sequence-shards the payload around
        # the ring; a ragged/decode sequence (S % ring != 0, e.g. S=1) can't
        # split, so THIS collective falls back to the monolithic allreduce
        # while the rest of the graph keeps the cais schedules
        if int(x.shape[1]) % self._ring(axis, cais) != 0:
            return prim.barrier_gemm_ar(x, w, axis)
        return prim.gemm_ar(x, w, axis, cais)

    def a2a_expert_ffn(self, send, ffn, axis, cais):
        if self.hierarchical(axis):
            return self.hier_a2a_expert_ffn(send, ffn, axis, cais)
        return prim.a2a_expert_ffn(send, ffn, axis, cais)

    def fused_rs_ln_ag(self, x, w1, ln_scale, w2, axis, cais,
                       norm="rmsnorm", residual=None):
        if self.hierarchical(axis):
            # base composition over this backend's guarded gemm_rs /
            # ag_gemm_multi — each tier plans its own leg inside those
            outs, z = super().fused_rs_ln_ag_multi(
                x, w1, ln_scale, (w2,), axis, cais, norm=norm,
                residual=residual)
            return outs[0], z
        # plan for the AG leg: the gathered z payload is (B, S, d) where
        # S = x.shape[1] (x is full-sequence, feature-sharded) and d = w1 cols
        n = self._ring(axis, cais)
        itemsize = np.dtype(x.dtype).itemsize
        z_bytes = int(x.shape[0]) * int(x.shape[1]) * int(w1.shape[1]) * \
            itemsize
        cais = self._resolve(cais, z_bytes, n)
        return prim.fused_rs_ln_ag(x, w1, ln_scale, w2, axis, cais,
                                   norm=norm, residual=residual)

    def fused_rs_ln_ag_multi(self, x, w1, ln_scale, ws2, axis, cais,
                             norm="rmsnorm", residual=None):
        if self.hierarchical(axis):
            return super().fused_rs_ln_ag_multi(x, w1, ln_scale, tuple(ws2),
                                                axis, cais, norm=norm,
                                                residual=residual)
        # same planning as fused_rs_ln_ag — the gathered z payload governs
        # both legs; with num_chunks resolved, the base-class composition
        # over this backend's gemm_rs / ag_gemm_multi is the schedule
        n = self._ring(axis, cais)
        itemsize = np.dtype(x.dtype).itemsize
        z_bytes = int(x.shape[0]) * int(x.shape[1]) * int(w1.shape[1]) * \
            itemsize
        cais = self._resolve(cais, z_bytes, n)
        return super().fused_rs_ln_ag_multi(x, w1, ln_scale, tuple(ws2),
                                            axis, cais, norm=norm,
                                            residual=residual)

    def fused_rs_ln(self, x, w1, ln_scale, axis, cais,
                    norm="rmsnorm", residual=None):
        if self.hierarchical(axis):
            return super().fused_rs_ln(x, w1, ln_scale, axis, cais,
                                       norm=norm, residual=residual)
        # plan for the RS leg like fused_rs_ln_ag: the z payload the ring
        # moves is (B, S, d) with d = w1 cols
        n = self._ring(axis, cais)
        itemsize = np.dtype(x.dtype).itemsize
        z_bytes = int(x.shape[0]) * int(x.shape[1]) * int(w1.shape[1]) * \
            itemsize
        cais = self._resolve(cais, z_bytes, n)
        return super().fused_rs_ln(x, w1, ln_scale, axis, cais, norm=norm,
                                   residual=residual)

    def grad_ag_gemm(self, d, wT, axis, cais):
        if self.hierarchical(axis):
            return self.hier_grad_ag_gemm(d, wT, axis, cais)
        # decomposed bidirectional ring gather of the cotangent, then the
        # GEMM against the transposed shard — the grad-side mirror of the
        # forward pull alignment
        n = self._ring(axis, cais)
        cais = self._resolve(cais, self._nbytes(d) * n, n)
        g = prim.ring_all_gather(d, axis, cais)
        return g @ wT, g

    def grad_a2a_expert_ffn(self, send, gy, bwd_row, axis, cais):
        if self.hierarchical(axis):
            return self.hier_grad_a2a_expert_ffn(send, gy, bwd_row, axis,
                                                 cais)
        # interleaved per-offset ± schedule mirroring the forward a2a; the
        # chunking is structural (one (row, cotangent) pair per offset —
        # splitting a row along C would break the E_loc·cap expert
        # segmentation), so no _resolve here; the planner prices the 2×
        # dispatch payload instead (plan/lower.py)
        return prim.grad_a2a_expert_ffn(send, gy, bwd_row, axis, cais)

    def overlap_asymmetric(self, rs_args, ag_args, axis, cais):
        if self.hierarchical(axis):
            return self.hier_overlap_asymmetric(rs_args, ag_args, axis, cais)
        # no _resolve: the lockstep schedule moves one S_loc slice per hop
        # on each stream — its chunking is structural, not planner-chosen
        return prim.overlap_asymmetric(rs_args, ag_args, axis, cais)


# ---------------------------------------------------------------------------
# auto — XLA-scheduled reference
# ---------------------------------------------------------------------------


class AutoBackend(BarrierBackend):
    """Defer scheduling to the compiler. ``explicit = False`` tells the model
    path to skip shard_map and express TP purely via sharding constraints
    (the strong compiler baseline); the inherited monolithic methods remain
    available so graphs can still be executed under this backend."""

    name = "auto"
    explicit = False


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, CollectiveBackend] = {}


def register_backend(backend: CollectiveBackend,
                     name: Optional[str] = None) -> CollectiveBackend:
    """Register (or replace) a backend under ``name or backend.name``."""
    key = name or backend.name
    if not key or key == "abstract":
        raise ValueError("backend must carry a concrete name")
    _REGISTRY[key] = backend
    return backend


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_backend(name: Union[str, CollectiveBackend]) -> CollectiveBackend:
    """Resolve a backend by name (instances pass through unchanged)."""
    if isinstance(name, CollectiveBackend):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown collective backend {name!r}; available: "
            f"{available_backends()}") from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


register_backend(BarrierBackend())
register_backend(CAISBackend())
register_backend(AutoBackend())
