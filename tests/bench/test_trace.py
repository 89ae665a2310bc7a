"""The reduction from a profiler trace to busy time, op classes, exposed
collectives and labelled idle gaps: interval arithmetic by hand, and a
small trace recorded on a v5e 2x2 host by ``record_fixture.py`` (three
steps of a matmul, an all-reduce and a ring permute over four chips, each
followed by 20 ms of host sleep under a ``bench.sleep`` span)."""
from __future__ import annotations

import pathlib

import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "ring4.xplane.pb"


def test_union_and_subtract():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.total([(0, 2), (3, 4)]) == 3
    assert trace.subtract([(0, 10)], [(1, 2), (5, 6)]) == \
        [(0, 1), (2, 5), (6, 10)]
    assert trace.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == \
        [(0, 0.5), (2.5, 3)]


def test_op_classes_from_hlo_text():
    T = "bf16[4,2048]{1,0:T(8,128)(2,1)}"
    assert trace.op_class(f"%all-reduce-start.2 = {T} all-reduce-start(%x)") \
        == "collective"
    assert trace.op_class(
        f"%collective-permute-done.1 = {T} collective-permute-done(%p)") \
        == "collective"
    assert trace.op_class(f"%fusion.12 = {T} fusion(%a), kind=kOutput, "
                          "calls=%all-reduce-scatter.3") == "collective"
    assert trace.op_class(f"%fusion.3 = {T} fusion(%all-gather-done.1), "
                          "kind=kOutput, calls=%fused_computation.3") \
        == "matmul"
    assert trace.op_class(f"%convolution.1 = {T} convolution(%a, %b)") \
        == "matmul"
    assert trace.op_class(f"%fusion.7 = {T} fusion(%a), kind=kLoop") \
        == "other"
    assert trace.op_class(f"%while.1 = (s32[]{{:T(128)}}, {T}) while("
                          "(s32[], bf16[4]) %t), body=%b") == "container"
    assert trace.parse_op(f"%copy.1 = {T} copy(%a)") == ("copy.1", "copy", "")
    assert trace.short_name(f"%fusion.9 = {T} fusion(%a), kind=kOutput") \
        == "fusion.9 fusion kOutput"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_file(str(FIXTURE))


def test_fixture_devices_and_window(reduced):
    assert reduced["devices"] == 4
    assert reduced["steps"] == {"bench.step": 3, "bench.sleep": 3}
    # three sleeps of 20 ms lie inside the window
    assert reduced["window_s"] > 0.06
    assert 0 < reduced["busy_s"] < reduced["window_s"] - 0.055


def test_fixture_op_classes(reduced):
    ops = reduced["op_s"]
    assert ops["matmul"] > 0 and ops["collective"] > 0
    assert sum(ops.values()) >= reduced["busy_s"] * 0.999
    assert 0 <= reduced["exposed_collective_s"] <= ops["collective"] * 1.001
    names = [n for n, _ in reduced["device_ops"]]
    # breakdown names are "<instruction> <opcode> [<fusion kind>]"
    assert len(names) <= 10
    assert any(trace.COLLECTIVE_OPS.match(n.split()[1]) for n in names)
    assert any(n.split()[1:] == ["fusion", "kOutput"] for n in names)


def test_fixture_idle_gaps_name_the_host_sleep(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) <= 10
    long = [label for label, s in gaps if s >= 0.015]
    assert len(long) >= 3
    assert all(label.startswith("bench.sleep") for label in long[:3])
    assert sorted((s for _, s in gaps), reverse=True) == [s for _, s in gaps]
