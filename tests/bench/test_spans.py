"""Device time by the program's names and the trainer's host phases
(``bench/spans.py``): the join of trace ops to compiled-text names, label
times, exposed collectives, unresolved time and host gaps, on a profile
built by hand with known intervals; and the join on a trace recorded on a
v5e 2x2 host by ``record_scopes.py`` (one smoke-width block's forward and
backward graphs through ``sp_period`` over four chips), with the text of
the module it ran."""
from __future__ import annotations

import gzip
import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import spans

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "period4"

US = 1000  # ns
T = "bf16[8]{0}"
STEP = "jit(train_step)/"
PERIOD = "while/body/closed_call/checkpoint/shard_map/tp.period/"
HLO = f"""HloModule jit_train_step, entry_computation_layout={{()->()}}

%fused_computation (p: bf16[8]) -> bf16[8] {{
  %p = {T} parameter(0)
  ROOT %m = {T} multiply(%p, %p), metadata={{op_name="{STEP}jvp()/df.x.y/mul"}}
}}

ENTRY %main () -> () {{
  %fusion.1 = {T} fusion(%a), kind=kOutput, calls=%fused_computation, metadata={{op_name="{STEP}jvp()/{PERIOD}df.ag_gemm_multi.b0.q+b0.k+b0.v/dot_general" stack_frame_id=3}}
  %all-gather-start.2 = ({T}, {T}) all-gather-start(%x), metadata={{op_name="{STEP}jvp()/{PERIOD}df.ag_gemm_multi.b0.q+b0.k+b0.v/all_gather"}}
  %all-gather-done.2 = {T} all-gather-done(%s), metadata={{op_name="{STEP}jvp()/{PERIOD}df.ag_gemm_multi.b0.q+b0.k+b0.v/all_gather"}}
  %fusion.3 = {T} fusion(%b), kind=kOutput, calls=%c, metadata={{op_name="{STEP}transpose(jvp())/{PERIOD}df.bwd_ag_gemm.adj.b0.rs2/dot_general"}}
  %collective-permute.4 = {T} collective-permute(%y), metadata={{op_name="{STEP}transpose(jvp())/{PERIOD}df.bwd_ag_gemm.adj.b0.rs2/ppermute"}}
  %all-reduce.5 = {T} all-reduce(%z), to_apply=%add, metadata={{op_name="{STEP}transpose(jvp())/{PERIOD}psum"}}
  %fusion.6 = {T} fusion(%d), kind=kLoop, calls=%e, metadata={{op_name="{STEP}transpose(jvp(model.head))/mul"}}
  %fusion.7 = {T} fusion(%f), kind=kLoop, calls=%g, metadata={{op_name="{STEP}step.optimizer/sqrt"}}
  %convert.8 = {T} convert(%h), metadata={{op_name="{STEP}transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/convert_element_type"}}
  %copy.9 = {T} copy(%i)
  ROOT %while.10 = {T} while(%j), condition=%k, body=%l, metadata={{op_name="{STEP}jvp()/while"}}
}}
"""


def _op(name):
    """A trace op event's name: its instruction's text."""
    for line in HLO.splitlines():
        if f"%{name} = " in line:
            return line.strip().removeprefix("ROOT ")
    return f"%{name} = {T} custom-call(%q)"


def _ev(name, a, b):
    return NS(name=name, start_ns=a * US, duration_ns=(b - a) * US)


def _device(n, ag_done_end):
    ops = [("while.10", 100, 500), ("fusion.1", 100, 200),
           ("all-gather-start.2", 200, 210),
           ("all-gather-done.2", 210, ag_done_end),
           ("fusion.3", 300, 400), ("collective-permute.4", 350, 420),
           ("all-reduce.5", 420, 440), ("fusion.6", 500, 550),
           ("fusion.7", 550, 600), ("convert.8", 600, 610),
           ("copy.9", 610, 620), ("mystery.11", 620, 640),
           # the step's instruction name, run by another module
           ("fusion.1", 700, 710),
           # outside the window
           ("fusion.1", 1100, 1200)]
    modules = [_ev("jit_train_step(123)", 90, 650),
               _ev("jit_other(7)", 700, 720)]
    return NS(name=f"/device:TPU:{n}", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=[_ev(_op(o), a, b) for o, a, b in ops])])


def _host():
    evs = [("bench.window", 0, 1000), ("train.batch", 10, 20),
           ("train.dispatch", 20, 40), ("train.sync", 40, 400),
           ("train.batch", 410, 425), ("train.dispatch", 430, 450),
           ("train.sync", 450, 900), ("train.dispatch", 1500, 1510)]
    return NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev(n, a, b) for n, a, b in evs])])


@pytest.fixture(scope="module")
def sp():
    pd = NS(planes=[_device(0, 260), _device(1, 240), _host()])
    return spans.reduce_profile(pd, HLO)


def test_scope_map_and_labels():
    module, names = spans.scope_map(HLO)
    assert module == "jit_train_step"
    assert names["copy.9"] == "" and names["m"].endswith("df.x.y/mul")
    assert spans.label_of(names["fusion.1"]) == \
        ("df.ag_gemm_multi", "b0.q+b0.k+b0.v", "fwd")
    assert spans.label_of(names["fusion.3"]) == \
        ("df.bwd_ag_gemm", "adj.b0.rs2", "bwd")
    assert spans.label_of(names["all-reduce.5"]) == ("tp.period", "", "bwd")
    assert spans.label_of(names["fusion.6"]) == ("model.head", "", "bwd")
    assert spans.label_of("jit(f)/jvp(model.embed)/gather") == \
        ("model.embed", "", "fwd")
    assert spans.label_of(names["convert.8"]) == ("unscoped", "", "remat")
    assert spans.label_of("") == ("unscoped", "", "fwd")


def test_label_times_average_over_chips(sp):
    assert sp["devices"] == 2 and sp["steps"] == 2
    assert sp["window_s"] == pytest.approx(1000e-6)
    lab = sp["labels"]
    # chip 0's all-gather wait ends at 260 us, chip 1's at 240 us
    assert lab["df.ag_gemm_multi"]["s"] == pytest.approx(150e-6)
    assert lab["df.ag_gemm_multi"]["collective_s"] == pytest.approx(50e-6)
    assert lab["df.ag_gemm_multi"]["exposed_s"] == pytest.approx(50e-6)
    assert lab["df.ag_gemm_multi"]["nodes"] == 1
    assert lab["df.bwd_ag_gemm"]["s"] == pytest.approx(120e-6)
    assert lab["df.bwd_ag_gemm"]["collective_s"] == pytest.approx(70e-6)
    assert lab["df.bwd_ag_gemm"]["exposed_s"] == pytest.approx(20e-6)
    assert lab["df.bwd_ag_gemm"]["phase_s"] == {"bwd": pytest.approx(120e-6)}
    assert lab["tp.period"]["exposed_s"] == pytest.approx(20e-6)
    # the while's time with no body op running is its own (unscoped)
    assert lab["unscoped"]["s"] == pytest.approx(130e-6)
    assert lab["unscoped"]["phase_s"] == {"fwd": pytest.approx(120e-6),
                                          "remat": pytest.approx(10e-6)}
    assert lab["model.head"]["s"] == pytest.approx(50e-6)
    assert lab["step.optimizer"]["s"] == pytest.approx(50e-6)
    # an unknown name, and a known name in another module
    assert sp["unresolved_s"] == pytest.approx(30e-6)
    assert [n for n, _ in sp["unresolved_ops"]] == \
        ["mystery.11 custom-call", "fusion.1 fusion kOutput"]
    assert sp["busy_s"] == pytest.approx(550e-6)
    assert sum(r["s"] for r in lab.values()) == pytest.approx(sp["busy_s"])


def test_graph_exposed_and_outside_graph(sp):
    assert sp["graph_exposed_s"] == pytest.approx(70e-6)
    assert sp["outside_graph_s"] == pytest.approx(280e-6)


def test_host_phases(sp):
    host = sp["host"]
    assert host["steps"] == 2
    assert host["gap_s"] == [pytest.approx(30e-6)]
    assert host["step_s"] == [pytest.approx(390e-6), pytest.approx(490e-6)]
    assert host["phase_s"] == {"train.batch": pytest.approx(25e-6),
                               "train.dispatch": pytest.approx(40e-6),
                               "train.sync": pytest.approx(810e-6)}


def test_readings_and_table(sp):
    got = spans.readings(sp, chips=2)
    assert got == {"graph_exposed_ms_per_step": pytest.approx(0.035),
                   "outside_graph_ms_per_step": pytest.approx(0.14),
                   "host_gap_ms_per_step": pytest.approx(0.03)}
    one = spans.readings(sp, chips=1)
    assert one["graph_exposed_ms_per_step"] is None
    assert one["outside_graph_ms_per_step"] is None
    assert one["host_gap_ms_per_step"] == pytest.approx(0.03)
    assert spans.readings(None, chips=4) == dict.fromkeys(one)
    table = spans.node_table(sp)
    assert table["df.bwd_ag_gemm"]["exposed_ms"] == pytest.approx(0.01)
    assert table["df.ag_gemm_multi"]["nodes"] == 1
    assert spans.node_table(None) is None


def test_a_program_without_names_reads_nothing():
    """The trace of a program that names no node and marks no phase:
    every op unscoped, no step, nothing to read."""
    bare = "\n".join(ln.split(", metadata=")[0] for ln in HLO.splitlines())
    pd = NS(planes=[_device(0, 260), NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev("bench.window", 0, 1000)])])])
    sp = spans.reduce_profile(pd, bare)
    assert set(sp["labels"]) == {"unscoped", "unresolved"}
    assert sp["steps"] == 0 and sp["host"]["gap_s"] == []
    assert spans.readings(sp, chips=4) == dict.fromkeys(
        spans.readings(None, chips=4))
    assert spans.node_table(sp) is None


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(f"{RECORDED}.xplane.pb.gz") as fh:
        pd = ProfileData.from_serialized_xspace(fh.read())
    with gzip.open(f"{RECORDED}.hlo.txt.gz", "rt") as fh:
        text = fh.read()
    return spans.reduce_profile(pd, text), text


def test_recorded_trace_joins_the_compiled_text(recorded):
    sp, text = recorded
    assert sp["devices"] == 4 and sp["steps"] == 2
    assert len(sp["host"]["gap_s"]) == 1
    total = sum(r["s"] for r in sp["labels"].values())
    assert total == pytest.approx(sp["busy_s"], rel=1e-9)
    assert sp["unresolved_s"] <= 0.01 * total
    kinds = {ln.split("/df.")[1].split(".")[0] for ln in text.splitlines()
             if "/df." in ln}
    assert {"ag_gemm_multi", "gemm_rs", "fused_rs_ln_ag_multi",
            "overlap_asym", "bwd_ag_gemm", "custom"} <= kinds
    assert {k for k in kinds if not sp["labels"].get(f"df.{k}", {}).get(
        "s")} == set()
    assert sp["labels"]["tp.period"]["s"] > 0
    phases = {p for lab, row in sp["labels"].items()
              if lab.startswith("df.") for p in row["phase_s"]}
    assert phases == {"fwd", "bwd"}


def test_reduce_dir_reads_what_the_tracer_wrote(recorded, tmp_path):
    """The trace as ``bench.trace.Tracer`` leaves it on disk, found under
    its directory; nothing where there is no trace."""
    sp, text = recorded
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    with gzip.open(f"{RECORDED}.xplane.pb.gz") as fh:
        (run / "host.xplane.pb").write_bytes(fh.read())
    assert spans.reduce_dir(str(tmp_path), text) == sp
    assert spans.reduce_dir(str(tmp_path / "none"), text) is None
