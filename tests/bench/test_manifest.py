"""BENCHMARK.json against the benchmark's contract, and the harness's
promise that a cell, configuration, traffic mix or per-layer metric is
added by adding files and entries alone."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH = re.compile(r"(_dim|_rank)$|^(hidden|intermediate|moe_intermediate|"
                   r"head|latent|state|projection)|expan|experts_per_tok")


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest()


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(man):
    assert set(man) == TOP_KEYS
    assert man["command"] == ["python3", "bench/run.py"]
    assert isinstance(man["run_seconds"], int)
    assert 1 <= man["run_seconds"] <= 51
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and ".." not in p
        assert (mf.ROOT / p).is_dir()
    assert len(json.dumps(man)) < 64 * 1024


def test_names_units_and_sources(man):
    groups = [man["configs"], man["workloads"], man["end_to_end"],
              man["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _one_line(m["layer"])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    setup = [m for m in man["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25 and "workloads" not in setup[0]


def test_every_cell_resolves_its_files(man):
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        cfg = mf.load_config(man, w["config"])
        tr = mf.load_traffic(w["traffic"])
        assert (mf.BENCH / "runners" / f"{tr['kind']}.py").is_file()
        mf.runner(tr["kind"])
        limits = mf.load_limits(w["name"])
        assert limits["limits"]
        assert cfg["name"] == w["config"]
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    fours = sum(w["chips"] == 4 for w in man["workloads"])
    assert fours <= max(1, len(man["workloads"]) // 2)


def test_configs_state_their_cuts(man):
    used = {w["config"] for w in man["workloads"]}
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for c in man["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        assert _one_line(c["source"]) and _one_line(c["why"])
        cfg = mf.load_config(man, c["name"])
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert cfg[key] == cfg["reduced"][key]["to"]
        assert cfg["source"] == c["source"]


def test_metric_readers_resolve_and_cover_their_cells(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}

    def reports(cell, metric):
        return cell in e2e[metric].get("workloads", cells)

    for m in man["per_layer"]:
        mf.metric_reader(m["name"])
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert reports(cell, m["moves"]), (m["name"], cell)
    for cell in cells:
        own = [n for n in e2e if reports(cell, n)]
        assert "setup_s" in own and len(own) >= 2
        assert mf.metrics_of(man, cell, "per_layer")


def test_a_cell_is_added_by_files_and_entries_alone(man, tmp_path,
                                                    monkeypatch):
    """Copy the benchmark, add a configuration, a traffic mix, a metric and
    a cell as new files and entries, and find them all by name, with every
    file that was there left byte for byte as it was."""
    bench = tmp_path / "bench"
    shutil.copytree(mf.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "deepseek-7b-tp4.json").read_text())
    cfg["name"] = "new-model"
    (bench / "configs" / "new-model.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "train-b4-s4096.json").read_text())
    tr["batch"] = 99
    (bench / "traffic" / "train-b99.json").write_text(json.dumps(tr))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    (bench / "limits" / "new-cell.json").write_text(
        json.dumps({"limits": {"gap": {"limit": 1.0}}}))
    new = json.loads(json.dumps(man))
    new["configs"].append({"name": "new-model", "source": "x",
                           "file": "bench/configs/new-model.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "new-cell", "config": "new-model",
                             "traffic": "train-b99", "chips": 1,
                             "why": "x"})
    new["per_layer"].append({"name": "new_metric", "unit": "%",
                             "better": "higher", "source": "host_clock",
                             "layer": "device", "moves": "setup_s",
                             "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    monkeypatch.setattr(mf, "BENCH", bench)
    monkeypatch.setattr(mf, "ROOT", tmp_path)
    got = mf.load_manifest(tmp_path / "BENCHMARK.json")
    cell = mf.cell(got, "new-cell")
    assert mf.load_config(got, cell["config"])["name"] == "new-model"
    assert mf.load_traffic(cell["traffic"])["batch"] == 99
    assert mf.load_limits("new-cell")["limits"]["gap"]["limit"] == 1.0
    assert mf.metric_reader("new_metric")({}) == 42.0
    assert [m["name"] for m in mf.metrics_of(got, "new-cell", "per_layer")] \
        == ["new_metric"]
    for p, data in before.items():
        assert p.read_bytes() == data, p
