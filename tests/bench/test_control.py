"""The control comes out not ``correct`` under each cell's limits
(``bench/limits/``): the reference computed in float8, put in the
program's place, at smoke size on the CPU. The chip readings that set the
limits are made at the cells' own sizes by ``bench/calibrate.py``."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import check, program, weights
from bench.reference import serve as ref_serve, train as ref_train
from smoke_cells import SERVE_CELL, cell_files, smoke_config, smoke_traffic

TRAIN_CELLS = ["ring-train-deepseek7b-cais"]


def _layout(cfg):
    from repro.models import build_model

    arch = program.arch_of(cfg)
    model = build_model(arch, program.runtime_of(cfg, arch))
    return jax.eval_shape(model.init, weights.seed_key(0))


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_control_is_not_correct_train(cell):
    _, cfg, job, limits = cell_files(cell)
    cfg, job = smoke_config(cfg), smoke_traffic(job)
    layout = _layout(cfg)
    seed = 2 ** 31 + 77
    ref = ref_train.readings(cfg, job, seed, layout, job["checked_steps"])
    ctl = ref_train.readings(cfg, job, seed, layout, job["checked_steps"],
                             quant=True)
    ok, checks = check.verdict(check.train_numbers(ctl, ref), limits)
    assert not ok, checks


def test_control_is_not_correct_serve():
    """At eight layers and some 500 read positions, where float8's error
    has room to build up as it does over the cell's 24 layers."""
    _, cfg, _, limits = cell_files(SERVE_CELL["name"])
    cfg = smoke_config(cfg)
    cfg.update(num_hidden_layers=8, hidden_size=128, intermediate_size=512,
               vocab_size=4096)
    layout = _layout(cfg)
    rng = np.random.default_rng(5)
    served = [(rng.integers(1, 4096, 48).astype(np.int32),
               list(rng.integers(1, 4096, 64))) for _ in range(8)]
    got = ref_serve.gaps(cfg, 9, layout, served, 128, 512, control=True)
    ok, checks = check.verdict({"gap": got["control_gap"]}, limits)
    assert not ok, checks
