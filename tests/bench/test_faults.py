"""Each fault a cell can have makes ``correct`` come out false, driving
the rest of a run at smoke size on the CPU: a step that returns its state
unchanged and half of the batch left out with the mean taken over the
rest (the training cell; ``test_ring_fault.py`` leaves out the exchange
between chips), and a served token altered where it is sampled (the
serving runner)."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from smoke_cells import SERVE_CELL, run_smoke


def _train_step_fault(monkeypatch, fault):
    import repro.train.trainer as trainer_mod

    make = trainer_mod.make_train_step

    def faulty(model, opt, rt, microbatches=1):
        step = make(model, opt, rt, microbatches)

        def run(state, batch):
            if fault == "half_batch":
                n = batch["labels"].size
                mask = (jnp.arange(n) < n // 2).astype(jnp.float32)
                batch = dict(batch, mask=mask.reshape(batch["labels"].shape))
            new, metrics = step(state, batch)
            return (state if fault == "unchanged" else new), metrics
        return run

    monkeypatch.setattr(trainer_mod, "make_train_step", faulty)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults_are_not_correct(monkeypatch, fault):
    _train_step_fault(monkeypatch, fault)
    line = run_smoke("ring-train-deepseek7b-cais")
    assert not line["correct"], line["checks"]


def test_altered_token_is_not_correct(monkeypatch):
    import repro.serve.engine as engine_mod

    sample = engine_mod._sample_token

    def altered(logits_row, seed, rid, token_index, temperature):
        tok = sample(logits_row, seed, rid, token_index, temperature)
        return (tok + 1) % len(logits_row) if token_index == 1 else tok

    monkeypatch.setattr(engine_mod, "_sample_token", altered)
    line = run_smoke(SERVE_CELL["name"])
    assert not line["correct"], line["checks"]
