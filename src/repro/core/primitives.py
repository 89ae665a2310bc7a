"""CAIS-on-TPU core primitives: decomposed collective-fused GEMM schedules.

The paper's insight (DESIGN.md §2): communication must follow the compute
kernel's memory semantics so data is consumed/produced chunk-by-chunk with no
global barrier between the collective and the GEMM.

On a TPU torus that lowers to *ring schedules of ``collective_permute``
interleaved with partial GEMMs* inside ``shard_map``:

  * :func:`ag_gemm`   — pull-aligned AllGather→GEMM (the paper's ld.cais):
    each ring step's arriving activation chunk is immediately consumed by a
    partial GEMM; XLA's latency-hiding scheduler overlaps permute *k+1* with
    dot *k* (the HLO shows ``collective-permute-start/done`` straddling dots).
  * :func:`gemm_rs`   — push-aligned GEMM→ReduceScatter (the paper's
    red.cais): a rotating accumulator is summed "in flight" hop by hop — the
    ring is the merge unit.
  * :func:`gemm_ar`   — AR = RS + AG, as the paper decomposes it.
  * :func:`fused_rs_ln_ag` — the graph-level optimizer's target chain
    GEMM-RS + LN + AG-GEMM (paper sub-layers L1–L4) in one pipeline.
  * ``barrier_*``     — the NVLS-style baselines: one monolithic collective
    HLO op around the GEMM (communication as an opaque phase).

``num_chunks`` micro-chunks the local shard so each permute carries
``payload/num_chunks`` bytes — the per-step staging buffer is the merge-table
analogue (paper Fig. 13/14). ``bidirectional=True`` splits micro-chunks
across the two ring directions (full-duplex ICI), the asymmetric-overlap
analogue (paper Fig. 9e/10).

All functions here run INSIDE ``shard_map`` (they use ``lax.axis_index`` /
``lax.ppermute``). ``repro.core.tp`` wraps them for pjit callers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


DEFAULT_NUM_CHUNKS = 4


@dataclass(frozen=True)
class CAISConfig:
    """Chunking/scheduling knobs (see repro.core.coordination).

    ``num_chunks=None`` leaves the chunking open: the ``cais``
    :mod:`repro.core.backends` backend then plans it per collective from
    payload bytes and ring size via ``coordination.plan``; primitives called
    directly fall back to ``DEFAULT_NUM_CHUNKS``. An explicit integer is a
    static override honored everywhere.

    ``hw`` is the :class:`repro.hw.HWSpec` the planner plans against
    (``None`` → the default V5E). On hierarchical 2D meshes the backend
    plans inter-node legs with ``hw.inter_tier()`` — the satellite fix for
    planning every axis against the flat-ring bandwidth."""

    num_chunks: Optional[int] = None   # micro-chunks per local shard
    bidirectional: bool = True         # use both ring directions
    interpret_n: Optional[int] = None  # override ring size (tests)
    hw: Optional[object] = None        # repro.hw.HWSpec for chunk planning


def _ring_perms(n: int, direction: int) -> Sequence[Tuple[int, int]]:
    if direction > 0:
        return [(i, (i + 1) % n) for i in range(n)]
    return [(i, (i - 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# Barrier (NVLS-style) baselines — one opaque collective around the GEMM
# ---------------------------------------------------------------------------


def barrier_ag_gemm(x: jnp.ndarray, w: jnp.ndarray, axis: str) -> jnp.ndarray:
    """x: (B, S_loc, d) seq-sharded; w: (d, F_loc). Returns (B, S, F_loc).

    ``all_gather`` completes in full before the GEMM starts — the
    communication-centric phase structure of TP-NVLS/SP-NVLS."""
    xg = lax.all_gather(x, axis, axis=1, tiled=True)  # (B, S, d)
    return xg @ w


def barrier_gemm_rs(x: jnp.ndarray, w: jnp.ndarray, axis: str) -> jnp.ndarray:
    """x: (B, S, d_loc) feature-sharded; w: (d_loc, F). Returns (B, S_loc, F)
    reduced over the axis and scattered on S."""
    y = x @ w                                    # full-size partial product
    return lax.psum_scatter(y, axis, scatter_dimension=1, tiled=True)


def barrier_gemm_ar(x: jnp.ndarray, w: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Basic-TP row-parallel GEMM + AllReduce."""
    return lax.psum(x @ w, axis)


# ---------------------------------------------------------------------------
# CAIS AG-GEMM: pull-aligned decomposed all-gather matmul
# ---------------------------------------------------------------------------


def ag_gemm_multi(x: jnp.ndarray, ws: Sequence[jnp.ndarray], axis: str,
                  cais: CAISConfig = CAISConfig()) -> Tuple[jnp.ndarray, ...]:
    """Decomposed AllGather→GEMM against several weights sharing one gather
    (fused QKV / gate+up projections: the activation circulates once, every
    weight consumes each chunk).

    x: (B, S_loc, d) sequence-sharded input; ws[k]: (d, F_k_loc)
    column-sharded weights. Returns one (B, S_loc*n, F_k_loc) per weight —
    identical to ``barrier_ag_gemm`` per weight.
    """
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        return tuple(x @ w for w in ws)
    B, S_loc, d = x.shape
    i = lax.axis_index(axis)

    c = _pick_chunks(S_loc, cais.num_chunks)
    half = c // 2 if (cais.bidirectional and c >= 2) else c
    # (c, B, S_loc/c, d) micro-chunks
    xs = x.reshape(B, c, S_loc // c, d).transpose(1, 0, 2, 3)

    fwd = _ring_perms(n, +1)
    bwd = _ring_perms(n, -1)

    def step(carry, _):
        chunks = carry
        parts = []
        new_chunks = []
        for j in range(c):
            # consume the chunk we currently hold...
            parts.append(tuple(chunks[j] @ w for w in ws))
            # ...while its forward permute is in flight (data-independent)
            perm = fwd if j < half else bwd
            new_chunks.append(lax.ppermute(chunks[j], axis, perm))
        ys = tuple(jnp.stack([p[k] for p in parts]) for k in range(len(ws)))
        return tuple(new_chunks), ys  # per weight: (c, B, s, F_k)

    chunks0 = tuple(xs[j] for j in range(c))
    _, parts = lax.scan(step, chunks0, None, length=n)

    # Reassemble: at step t, micro-chunk j (direction ±1) originated at
    # device (i ∓ t) mod n — a pure ROTATION of the step axis, so ordering
    # is a roll (two slices + concat), not a scatter (§Perf iteration 6:
    # the scatter was the CAIS memory-term overhead).
    #   fwd: ordered[j] = parts[(i−j)%n] = roll(flip(parts), i+1)
    #   bwd: ordered[j] = parts[(j−i)%n] = roll(parts, i)
    outs = []
    for k in range(len(ws)):
        pk = parts[k]  # (n, c, B, s, F_k)
        out_rows = []
        for j in range(c):
            if j < half:
                ordered = jnp.roll(jnp.flip(pk[:, j], axis=0), i + 1, axis=0)
            else:
                ordered = jnp.roll(pk[:, j], i, axis=0)
            out_rows.append(ordered)  # (n, B, s, F)
        out = jnp.stack(out_rows, axis=1)
        # (n, c, B, s, F) -> (B, n*c*s, F) with row order (shard, chunk, s)
        outs.append(out.transpose(2, 0, 1, 3, 4).reshape(
            B, n * S_loc, ws[k].shape[1]))
    return tuple(outs)


def ag_gemm(x: jnp.ndarray, w: jnp.ndarray, axis: str,
            cais: CAISConfig = CAISConfig()) -> jnp.ndarray:
    """Decomposed AllGather→GEMM (single weight). See :func:`ag_gemm_multi`."""
    return ag_gemm_multi(x, (w,), axis, cais)[0]


def _pick_chunks(s_loc: int, requested: Optional[int]) -> int:
    if requested is None:
        requested = DEFAULT_NUM_CHUNKS
    c = max(1, min(requested, s_loc))
    while s_loc % c:
        c -= 1
    return c


# ---------------------------------------------------------------------------
# CAIS GEMM-RS: push-aligned decomposed matmul reduce-scatter
# ---------------------------------------------------------------------------


def gemm_rs(x: jnp.ndarray, w: jnp.ndarray, axis: str,
            cais: CAISConfig = CAISConfig()) -> jnp.ndarray:
    """Decomposed GEMM→ReduceScatter.

    x: (B, S, d_loc) feature-sharded input; w: (d_loc, F) row-sharded weight.
    Returns (B, S_loc, F): the reduced output scattered on the sequence —
    identical to ``barrier_gemm_rs``, but each output shard's partial GEMM is
    computed just-in-time as the rotating accumulator arrives (reduction "in
    flight": the ring hop is the merge unit).
    """
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        return x @ w
    B, S, d_loc = x.shape
    F = w.shape[1]
    S_loc = S // n
    i = lax.axis_index(axis)

    def partial(j):
        """Local partial product for destination shard j: (B, S_loc, F)."""
        xc = lax.dynamic_slice_in_dim(x, j * S_loc, S_loc, axis=1)
        return xc @ w

    if cais.bidirectional and n % 2 == 0 and S_loc % 2 == 0:
        # split S_loc rows in half; each half reduced around opposite rings
        # (odd S_loc can't split evenly — the unidirectional ring below
        # handles it; S_loc == 1 shows up on serve-period graphs at S == n)
        h = S_loc // 2

        def partial_half(j, lo):
            xc = lax.dynamic_slice_in_dim(x, j * S_loc + lo, h, axis=1)
            return xc @ w

        fwd = _ring_perms(n, +1)
        bwd = _ring_perms(n, -1)

        def step(carry, t):
            accf, accb = carry
            accf = lax.ppermute(accf, axis, fwd)
            accb = lax.ppermute(accb, axis, bwd)
            jf = (i - 1 - t) % n     # fwd acc now holds shard i-1-t
            jb = (i + 1 + t) % n     # bwd acc now holds shard i+1+t
            return (accf + partial_half(jf, 0),
                    accb + partial_half(jb, h)), None

        acc0 = (partial_half((i - 1) % n, 0), partial_half((i + 1) % n, h))
        (accf, accb), _ = lax.scan(step, acc0, jnp.arange(1, n))
        return jnp.concatenate([accf, accb], axis=1)

    fwd = _ring_perms(n, +1)

    def step(acc, t):
        acc = lax.ppermute(acc, axis, fwd)
        j = (i - 1 - t) % n
        return acc + partial(j), None

    acc0 = partial((i - 1) % n)
    acc, _ = lax.scan(step, acc0, jnp.arange(1, n))
    return acc


def gemm_ar(x: jnp.ndarray, w: jnp.ndarray, axis: str,
            cais: CAISConfig = CAISConfig()) -> jnp.ndarray:
    """Basic-TP GEMM→AllReduce as RS + AG (both decomposed).

    x: (B, S, d_loc); w: (d_loc, F). Returns (B, S, F) fully reduced."""
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        return x @ w
    y_loc = gemm_rs(x, w, axis, cais)       # (B, S_loc, F)
    return ring_all_gather(y_loc, axis, cais)


def ring_all_gather(x: jnp.ndarray, axis: str,
                    cais: CAISConfig = CAISConfig()) -> jnp.ndarray:
    """Decomposed (bidirectional) ring all-gather along dim 1."""
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        return x
    i = lax.axis_index(axis)
    B, S_loc = x.shape[0], x.shape[1]

    fwd = _ring_perms(n, +1)
    bwd = _ring_perms(n, -1)

    if cais.bidirectional and S_loc >= 2:
        h = S_loc // 2
        xf, xb = x[:, :h], x[:, h:]

        def step(carry, _):
            cf, cb = carry
            nf = lax.ppermute(cf, axis, fwd)
            nb = lax.ppermute(cb, axis, bwd)
            return (nf, nb), (cf, cb)

        _, (pf, pb) = lax.scan(step, (xf, xb), None, length=n)
        of = jnp.roll(jnp.flip(pf, axis=0), i + 1, axis=0)
        ob = jnp.roll(pb, i, axis=0)
        out = jnp.concatenate([of, ob], axis=2)
        return out.transpose(1, 0, *range(2, out.ndim)).reshape(
            B, n * S_loc, *x.shape[2:])

    def step(chunk, _):
        return lax.ppermute(chunk, axis, fwd), chunk

    _, parts = lax.scan(step, x, None, length=n)
    ordered = jnp.roll(jnp.flip(parts, axis=0), i + 1, axis=0)
    return ordered.transpose(1, 0, *range(2, ordered.ndim)).reshape(
        B, n * S_loc, *x.shape[2:])


def ring_reduce_scatter(x: jnp.ndarray, axis: str,
                        cais: CAISConfig = CAISConfig()) -> jnp.ndarray:
    """Decomposed (bidirectional) ring reduce-scatter along dim 1 — the
    standalone counterpart of :func:`gemm_rs`'s rotating accumulator, used
    as the outer-tier (inter-node) leg of hierarchical compositions where
    the GEMM already happened on the inner ring."""
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        return x
    S = x.shape[1]
    S_loc = S // n
    i = lax.axis_index(axis)

    def part(j, lo, ln):
        return lax.dynamic_slice_in_dim(x, j * S_loc + lo, ln, axis=1)

    if cais.bidirectional and n % 2 == 0 and S_loc % 2 == 0:
        h = S_loc // 2
        fwd = _ring_perms(n, +1)
        bwd = _ring_perms(n, -1)

        def step(carry, t):
            accf, accb = carry
            accf = lax.ppermute(accf, axis, fwd)
            accb = lax.ppermute(accb, axis, bwd)
            jf = (i - 1 - t) % n
            jb = (i + 1 + t) % n
            return (accf + part(jf, 0, h), accb + part(jb, h, h)), None

        acc0 = (part((i - 1) % n, 0, h), part((i + 1) % n, h, h))
        (accf, accb), _ = lax.scan(step, acc0, jnp.arange(1, n))
        return jnp.concatenate([accf, accb], axis=1)

    fwd = _ring_perms(n, +1)

    def step(acc, t):
        acc = lax.ppermute(acc, axis, fwd)
        return acc + part((i - 1 - t) % n, 0, S_loc), None

    acc, _ = lax.scan(step, part((i - 1) % n, 0, S_loc), jnp.arange(1, n))
    return acc


# ---------------------------------------------------------------------------
# CAIS expert all-to-all: decomposed dispatch/compute/combine pipeline (EP)
# ---------------------------------------------------------------------------


def barrier_a2a_expert_ffn(send: jnp.ndarray, ffn: Callable, axis: str
                           ) -> jnp.ndarray:
    """EP baseline: monolithic dispatch all-to-all → expert FFN → combine
    all-to-all (three isolated phases — the NVLS-style structure).

    send: (n, C, d) — send[j] holds this device's token chunk routed to the
    expert(s) owned by device j. ffn: (C, d) -> (C, d) local expert compute.
    Returns (n, C, d): out[j] = FFN_j(send[j]) (owner-j's experts applied)."""
    n = send.shape[0]
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                          tiled=False)
    h = jax.vmap(ffn)(recv)
    return lax.all_to_all(h, axis, split_axis=0, concat_axis=0, tiled=False)


def a2a_expert_ffn(send: jnp.ndarray, ffn: Callable, axis: str,
                   cais: CAISConfig = CAISConfig()) -> jnp.ndarray:
    """CAIS-decomposed expert all-to-all (beyond the paper: §Perf found the
    published technique leaves MoE's dominant collective untouched).

    Per offset o = 1..n−1 the dispatch permute (+o direction) of chunk o,
    the expert FFN on the chunk that just arrived, and the combine permute
    (−o direction) of the previous result are all in flight together — the
    dispatch and combine streams occupy OPPOSITE link directions every step
    (the asymmetric kernel overlap of paper Fig. 9e, applied to EP).

    Same contract as :func:`barrier_a2a_expert_ffn`. Note: offset-o permutes
    are single HLO ops that a torus lowers to ≤o hops; the dry-run's
    byte accounting counts payload once per permute (same as a2a's slices).
    """
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        return jax.vmap(ffn)(send)
    i = lax.axis_index(axis)
    C, d = send.shape[1], send.shape[2]

    def perm_for(offset: int):
        return [(s, (s + offset) % n) for s in range(n)]

    # local chunk computes immediately (no wire)
    out0 = ffn(_take_row(send, i))
    results = jnp.zeros_like(send)
    results = _dus_row(results, out0, i)

    for o in range(1, n):
        # alternate ± offsets so consecutive dispatches balance directions
        off = o if not cais.bidirectional else ((o + 1) // 2 if o % 2
                                                else -(o // 2))
        # dispatch chunk destined o "slots" away (direction ±)
        dst = (i + off) % n
        chunk = _take_row(send, dst)
        arrived = lax.ppermute(chunk, axis, perm_for(off))  # from (i-off)
        h = ffn(arrived)
        # combine travels the opposite direction back to the origin
        returned = lax.ppermute(h, axis, perm_for(-off))
        # `returned` is the FFN output of MY tokens computed by (i+off)
        results = _dus_row(results, returned, dst)
    return results


def _take_row(x: jnp.ndarray, idx) -> jnp.ndarray:
    return lax.dynamic_index_in_dim(x, idx, axis=0, keepdims=False)


def _dus_row(x: jnp.ndarray, row: jnp.ndarray, idx) -> jnp.ndarray:
    return lax.dynamic_update_index_in_dim(x, row, idx, axis=0)


def grad_a2a_expert_ffn(send: jnp.ndarray, gy: jnp.ndarray,
                        bwd_row: Callable, axis: str,
                        cais: CAISConfig = CAISConfig()):
    """CAIS-decomposed adjoint of :func:`a2a_expert_ffn`.

    Mirrors the forward's interleaved per-offset schedule: each step the
    grad-dispatch permute (+o direction) carries the (send row, output
    cotangent row) pair to the owning expert, the per-row expert VJP runs
    on the pair that just arrived, and the chunk-cotangent combine permute
    (−o direction) returns the previous result to its sender — dispatch
    and combine again ride OPPOSITE link directions every step. The
    dispatch payload is 2× the forward's (row + cotangent travel
    together); the planner prices both directions (plan/lower.py).

    ``bwd_row(chunk, gy_row) -> (d_chunk, dw_tuple)`` is the per-row
    expert VJP built by the executor. Expert weight grads accumulate
    LOCALLY at the owner — they never ride a collective. Returns
    ``(d_send, dw_tuple)`` with ``d_send`` shaped like ``send``.
    """
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        d_rows, dw_rows = jax.vmap(bwd_row)(send, gy)
        return d_rows, tuple(jnp.sum(a, axis=0) for a in dw_rows)
    i = lax.axis_index(axis)

    def perm_for(offset: int):
        return [(s, (s + offset) % n) for s in range(n)]

    # local row: my tokens routed to my own experts (no wire)
    d0, dws = bwd_row(_take_row(send, i), _take_row(gy, i))
    d_send = jnp.zeros_like(send)
    d_send = _dus_row(d_send, d0, i)

    for o in range(1, n):
        # same ± alternation as the forward so directions stay balanced
        off = o if not cais.bidirectional else ((o + 1) // 2 if o % 2
                                                else -(o // 2))
        dst = (i + off) % n
        # grad-dispatch: the row AND its output cotangent travel together
        arr_c = lax.ppermute(_take_row(send, dst), axis, perm_for(off))
        arr_g = lax.ppermute(_take_row(gy, dst), axis, perm_for(off))
        d_chunk, dw_o = bwd_row(arr_c, arr_g)  # my experts' VJP
        # chunk cotangent travels the opposite direction back to sender
        returned = lax.ppermute(d_chunk, axis, perm_for(-off))
        d_send = _dus_row(d_send, returned, dst)
        dws = tuple(a + b for a, b in zip(dws, dw_o))
    return d_send, dws


# ---------------------------------------------------------------------------
# Fused sub-layer: GEMM-RS + LN + AG-GEMM (the paper's L1–L4 chain)
# ---------------------------------------------------------------------------


def fused_rs_ln_ag(x: jnp.ndarray, w1: jnp.ndarray, ln_scale: jnp.ndarray,
                   w2: jnp.ndarray, axis: str,
                   cais: CAISConfig = CAISConfig(),
                   norm: str = "rmsnorm",
                   residual: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The graph-level dataflow optimizer's fused pipeline (DESIGN.md §4).

    x: (B, S, d1_loc) feature-sharded → GEMM-RS → (B, S_loc, d) → (+residual)
    → LN (sequence-parallel, no collective) → AG-GEMM → (B, S, d2_loc).

    The RS ring runs +1 and the AG ring −1 (and each is internally
    bidirectional), so both directions of every ICI link carry payload —
    the asymmetric kernel overlap of paper Fig. 9(e)/Fig. 10.
    """
    from repro.models.layers import apply_norm  # local import; no cycle

    z = gemm_rs(x, w1, axis, cais)                      # push-aligned
    if residual is not None:
        z = z + residual
    zn = apply_norm(norm, {"scale": ln_scale}, z)       # seq-sharded LN
    out = ag_gemm(zn, w2, axis, cais)                   # pull-aligned
    return out, z


# ---------------------------------------------------------------------------
# Asymmetric dual-stream overlap: two independent chains, opposite traffic
# ---------------------------------------------------------------------------


def overlap_asymmetric(rs_args, ag_args, axis: str,
                       cais: CAISConfig = CAISConfig()):
    """Run an independent GEMM-RS and AG-GEMM *in lockstep*, one scan: each
    step issues one RS hop (+1 ring) and one AG hop (−1 ring) plus both
    partial GEMMs. This is the direct analogue of the paper's asymmetric
    kernel overlapping (two kernels with complementary traffic sharing the
    link bidirectionally).

    rs_args: (x_rs (B,S,d_loc), w_rs (d_loc,F)); ag_args: (x_ag (B,S_loc,d),
    w_ag (d,F_loc) — or a tuple of such weights sharing the one AG
    circulation, e.g. a paired ``ag_gemm_multi``). Returns
    (rs_out (B,S_loc,F), ag_out (B,S,F_loc)) — ``ag_out`` is a tuple of
    per-weight outputs when ``w_ag`` is a tuple.
    """
    x_rs, w_rs = rs_args
    x_ag, w_ag = ag_args
    multi = isinstance(w_ag, (tuple, list))
    ws_ag = tuple(w_ag) if multi else (w_ag,)
    n = cais.interpret_n or lax.axis_size(axis)
    if n == 1:
        outs = tuple(x_ag @ w for w in ws_ag)
        return x_rs @ w_rs, (outs if multi else outs[0])
    i = lax.axis_index(axis)
    B, S, _ = x_rs.shape
    S_loc = S // n

    fwd = _ring_perms(n, +1)
    bwd = _ring_perms(n, -1)

    def rs_partial(j):
        xc = lax.dynamic_slice_in_dim(x_rs, j * S_loc, S_loc, axis=1)
        return xc @ w_rs

    def step(carry, t):
        acc, chunk = carry
        # RS stream on the +1 direction
        acc = lax.ppermute(acc, axis, fwd)
        acc = acc + rs_partial((i - 1 - t) % n)
        # AG stream on the −1 direction (data-independent of the RS stream)
        part = tuple(chunk @ w for w in ws_ag)
        chunk = lax.ppermute(chunk, axis, bwd)
        return (acc, chunk), part

    acc0 = rs_partial((i - 1) % n)
    part0 = tuple(x_ag @ w for w in ws_ag)
    chunk0 = lax.ppermute(x_ag, axis, bwd)
    (acc, _), parts = lax.scan(step, (acc0, chunk0), jnp.arange(1, n))

    ag_outs = []
    for k in range(len(ws_ag)):
        pk = jnp.concatenate([part0[k][None], parts[k]], axis=0)  # (n,B,s,F)
        ordered = jnp.roll(pk, i, axis=0)   # ordered[j] = parts[(j−i)%n]
        ag_outs.append(ordered.transpose(1, 0, 2, 3).reshape(B, n * S_loc, -1))
    return acc, (tuple(ag_outs) if multi else ag_outs[0])
