"""Hierarchical 2D-mesh TP (docs/topology.md) — the ISSUE-9 pins.

Tier-1 (single-device) layer: property-based invariants of the
``coordination.plan`` chunk scheduler across both fabric tiers, the
``HWSpec.inter_tier()`` / per-axis planning regression (the 2D-mesh
microbatch and chunk plans must be computed against the inter-node tier,
not the flat intra-node ring), and the composite-axis sharding helpers.

The 8-virtual-device parity sweep (flat ring ≡ 2D mesh for every
factorization × backend × shape, grouped-EP MoE, full-model fwd+grads)
lives in ``tests/topo_checks.py`` and runs as a subprocess under the
``multidev`` marker — the main pytest process keeps exactly one device.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from hypothesis import given
from hypothesis import strategies as st
from repro import sharding
from repro.core import coordination
from repro.hw import V5E

HERE = pathlib.Path(__file__).parent
REPO = HERE.parent

INTER = V5E.inter_tier()


# ---------------------------------------------------------------------------
# coordination.plan invariants (property layer)
# ---------------------------------------------------------------------------


@given(payload=st.sampled_from([1e3, 1e5, 1e7, 1e9]),
       ring=st.integers(2, 8),
       bidirectional=st.booleans(),
       inter=st.booleans())
def test_plan_chunk_bounds(payload, ring, bidirectional, inter):
    """chunks >= 1 always; chunks <= max_chunks unless the staging budget
    forced past the cap — and then the plan must say so (over_cap)."""
    hw = INTER if inter else V5E
    p = coordination.plan(payload, ring, bidirectional=bidirectional, hw=hw)
    assert p.num_chunks >= 1
    assert p.num_chunks <= 64 or p.over_cap
    assert p.staging_bytes >= 0 and p.total_comm >= 0.0


@given(ring=st.integers(2, 8),
       bidirectional=st.booleans(),
       inter=st.booleans())
def test_plan_monotone_in_payload(ring, bidirectional, inter):
    """At compute_time=0 a larger payload never plans FEWER chunks: both
    the latency bound and the staging bound scale up with the shard."""
    hw = INTER if inter else V5E
    payloads = [1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9]
    chunks = [coordination.plan(p, ring, bidirectional=bidirectional,
                                hw=hw).num_chunks for p in payloads]
    assert chunks == sorted(chunks), chunks


@given(payload=st.sampled_from([1e5, 1e7, 1e9]),
       ring=st.integers(2, 8))
def test_plan_inter_tier_plans_coarser(payload, ring):
    """The slow high-latency inter-node tier must never chunk finer than
    the intra-node ring for the same payload: the latency bound
    chunk >= alpha*beta*(1/maxfrac - 1) grows with alpha."""
    flat = coordination.plan(payload, ring, hw=V5E)
    inter = coordination.plan(payload, ring, hw=INTER)
    assert inter.num_chunks <= flat.num_chunks


# ---------------------------------------------------------------------------
# HWSpec inter-node tier + per-axis planning regression (ISSUE-9 fix)
# ---------------------------------------------------------------------------


def test_inter_tier_hwspec():
    assert V5E.dcn_bw < V5E.ici_bw
    assert V5E.dcn_latency > V5E.hop_latency
    assert INTER.ici_bw == V5E.dcn_bw
    assert INTER.hop_latency == V5E.dcn_latency
    # compute/memory side unchanged — only the fabric tier swaps
    assert INTER.peak_flops == V5E.peak_flops


def test_two_tier_hwspec_plan_regression():
    """A scaled-down two-tier HWSpec: the chunk plan for the SAME payload
    must differ between tiers (the bug this pins: feeding the flat-ring
    fabric to the planner on a 2D mesh silently over-chunks the slow
    tier)."""
    import dataclasses

    hw = dataclasses.replace(V5E, ici_bw=100e9, hop_latency=1e-6,
                             dcn_bw=10e9, dcn_latency=50e-6)
    payload = 64 * 1024 * 1024
    flat = coordination.plan(payload, 4, hw=hw)
    inter = coordination.plan(payload, 4, hw=hw.inter_tier())
    assert inter.num_chunks < flat.num_chunks, (inter, flat)


def test_plan_microbatches_inter_tier_splits_less():
    """plan_microbatches on the inter-node tier: the latency floor is ~50x
    higher, so the auto split must be no larger than the intra-node one
    (and strictly smaller at a payload near the floor)."""
    payload = 4 * 1024 * 1024
    mb_flat = coordination.plan_microbatches(8, payload, 4, hw=V5E)
    mb_inter = coordination.plan_microbatches(8, payload, 4, hw=INTER)
    assert mb_inter <= mb_flat
    assert coordination.plan_microbatches(8, 256 * 1024, 4, hw=INTER) == 1


def test_planned_chunks_cache_keyed_by_hw():
    """The cais auto-chunk memo must key on the hw tier: the same payload
    over the same ring plans differently per tier."""
    from repro.core.backends import _planned_chunks

    payload = 64 * 1024 * 1024
    flat = _planned_chunks(payload, 8, True, V5E)
    inter = _planned_chunks(payload, 8, True, INTER)
    assert flat != inter, (flat, inter)


# ---------------------------------------------------------------------------
# composite-axis sharding helpers (mesh-free paths)
# ---------------------------------------------------------------------------


def test_tp_axes_and_size_defaults():
    assert sharding.tp_axes(None) == sharding.MODEL_AXIS
    assert sharding.tp_size(None) == 1
    assert sharding.TP_AXES_2D == (sharding.TP_IN_AXIS, sharding.TP_OUT_AXIS)


def test_composite_flat_index_layout():
    """Layout contract (docs/topology.md): the composite ("tp_in",
    "tp_out") entry is tp_in-MAJOR — flattened shard s = i_in * O + i_out.
    Pin the pure-python mirror of shard_map_axis_index so the in-graph
    GQA head slicing and the PartitionSpec layout cannot drift apart."""
    I, O = 2, 4
    seen = []
    for i_in in range(I):
        for i_out in range(O):
            seen.append(i_in * O + i_out)
    assert seen == list(range(I * O))


# ---------------------------------------------------------------------------
# 8-virtual-device parity sweep (subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.multidev
def test_topology_suite():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, str(HERE / "topo_checks.py")],
        capture_output=True, text=True, env=env, timeout=1200,
        cwd=str(REPO))
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "2D-topology checks failed"
    assert "ALL OK" in proc.stdout
