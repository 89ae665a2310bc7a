"""Runs benchmark cells on the CPU at smoke widths, skipping the harness's
look for a chip: the same runners, comparison and limits as on the chip,
with every width, the batch and the traffic shrunk so a test holds it.

The serving runner has no cell in ``BENCHMARK.json`` yet. Its tests drive
it as ``SERVE_CELL``, on the serving configuration in ``bench/configs/``
with a mix and limits of their own from ``data/``."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "intermediate_size": 128, "vocab_size": 256,
         "num_hidden_layers": 2}

SERVE_CELL = {"name": "serve-chat-internlm2", "config": "internlm2-1.8b",
              "traffic": "serve-smoke", "chips": 1}


def config_file(name):
    """``bench/configs/<name>.json``, listed in BENCHMARK.json or not."""
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def smoke_config(cfg):
    c = copy.deepcopy(cfg)
    mha = cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    c.update(SMALL)
    c["num_key_value_heads"] = 4 if mha else 2
    if "serve" in c["program"]:
        c["program"]["serve"].update(max_batch=4, s_max=128,
                                     prefill_chunk=8, num_blocks=64)
    return c


def smoke_traffic(tr):
    t = copy.deepcopy(tr)
    if t["kind"] == "train":
        t.update(batch=4, seq_len=64, trace_steps=2)
    return t


def cell_files(cell_name):
    """``(cell, config, traffic, limits)`` of a cell at full size."""
    from bench import manifest as mf

    if cell_name == SERVE_CELL["name"]:
        return (SERVE_CELL, config_file(SERVE_CELL["config"]),
                json.loads((DATA / "serve-smoke.json").read_text()),
                json.loads((DATA / "serve-smoke.limits.json").read_text()))
    man = mf.load_manifest()
    cell = mf.cell(man, cell_name)
    return (cell, mf.load_config(man, cell["config"]),
            mf.load_traffic(cell["traffic"]), mf.load_limits(cell_name))


def run_smoke(cell_name, seed=2 ** 31 + 12345, seconds=1.0, trace=False):
    """The result line of ``cell_name`` run at smoke size on the CPU."""
    import jax

    import bench.run as R
    from bench import manifest as mf
    from bench.clock import CompileClock

    cell, cfg, tr, limits = cell_files(cell_name)
    peaks = mf._json(mf.BENCH / "peaks.json")["TPU v5 lite"]
    ctx = R.Context(
        cell=cell, config=smoke_config(cfg), traffic=smoke_traffic(tr),
        seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[:cell["chips"]], peaks=peaks,
        clock=CompileClock())
    return R.run_cell(ctx, mf.load_manifest(), limits)
