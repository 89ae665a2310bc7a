"""Substrate tests: data determinism, optimizers, trainer loop,
checkpoint/restart determinism, fault-tolerance hooks, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.configs import SHAPES_BY_NAME, ShapeConfig, get_arch
from repro.data.pipeline import DataConfig, make_batch
from repro.ft.watchdog import StepWatchdog
from repro.models import build_model
from repro.optim import (adafactor, adamw, clip_by_global_norm,
                         constant_schedule, cosine_schedule, make_optimizer)
from repro.runtime import SMOKE
from repro.serve import Engine, Request, ServeConfig
from repro.train import Trainer, TrainerConfig, init_state, make_train_step

TINY = ShapeConfig("tiny", 16, 4, "train")


def tiny_setup(arch="internlm2-1.8b", opt_name="adamw"):
    cfg = get_arch(arch).smoke()
    model = build_model(cfg, SMOKE)
    opt = make_optimizer(opt_name, constant_schedule(1e-3))
    return cfg, model, opt


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_data_deterministic_by_step():
    cfg = get_arch("deepseek-7b").smoke()
    a = make_batch(cfg, TINY, step=7)
    b = make_batch(cfg, TINY, step=7)
    c = make_batch(cfg, TINY, step=8)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].max() < cfg.vocab_size
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_data_host_slice():
    cfg = get_arch("deepseek-7b").smoke()
    full = make_batch(cfg, TINY, step=3)
    part = make_batch(cfg, TINY, step=3, host_slice=slice(1, 3))
    np.testing.assert_array_equal(full["tokens"][1:3], part["tokens"])


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_converges_quadratic(name):
    """Both optimizers should drive a toy quadratic toward its optimum."""
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3), "m": jnp.zeros((4, 3))}
    opt = make_optimizer(name, constant_schedule(5e-2), weight_decay=0.0)
    state = opt.init(params)

    def loss_fn(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["m"] ** 2)

    for step in range(300):
        grads = jax.grad(loss_fn)(params)
        params, state = opt.apply(params, grads, state,
                                  jnp.asarray(step, jnp.int32))
    assert float(loss_fn(params)) < 1e-2, float(loss_fn(params))


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 100
    from repro.optim import global_norm
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5


def test_cosine_schedule_shape():
    s = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(s(jnp.asarray(0))) == 0.0
    assert abs(float(s(jnp.asarray(10))) - 1e-3) < 1e-9
    assert float(s(jnp.asarray(100))) < 2e-4


# ---------------------------------------------------------------------------
# train step + trainer
# ---------------------------------------------------------------------------


def test_train_step_reduces_loss():
    cfg, model, opt = tiny_setup()
    step = jax.jit(make_train_step(model, opt, SMOKE))
    state = init_state(model, opt, jax.random.key(0))
    batch = make_batch(cfg, TINY, 0)
    losses = []
    for _ in range(30):
        state, m = step(state, batch)   # overfit one batch
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_microbatch_accumulation_matches_full():
    cfg, model, opt = tiny_setup()
    batch = make_batch(cfg, TINY, 0)
    s1 = init_state(model, opt, jax.random.key(0))
    s2 = jax.tree.map(jnp.copy, s1)
    full = jax.jit(make_train_step(model, opt, SMOKE, microbatches=1))
    micro = jax.jit(make_train_step(model, opt, SMOKE, microbatches=2))
    s1, m1 = full(s1, batch)
    s2, m2 = micro(s2, batch)
    # same data, same update (microbatches average to the same gradient —
    # up to clipping nonlinearity, loss must match closely)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_trainer_runs_and_checkpoints(tmp_path):
    cfg, model, opt = tiny_setup()
    tc = TrainerConfig(total_steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                       log_every=100)
    tr = Trainer(model, opt, cfg, TINY, SMOKE, tc)
    tr.run()
    assert store.latest_step(str(tmp_path)) == 6
    assert len(tr.history) == 6


def test_checkpoint_restart_determinism(tmp_path):
    """Kill at step 4, restart, finish — identical params to uninterrupted."""
    cfg, model, opt = tiny_setup()

    # uninterrupted run to step 8
    tr_full = Trainer(model, opt, cfg, TINY, SMOKE,
                      TrainerConfig(total_steps=8, log_every=100))
    state_full = tr_full.run()

    # interrupted: run 4, checkpoint, new trainer restores and finishes
    d = str(tmp_path)
    tr_a = Trainer(model, opt, cfg, TINY, SMOKE,
                   TrainerConfig(total_steps=4, ckpt_dir=d, ckpt_every=4,
                                 log_every=100))
    tr_a.run()
    tr_b = Trainer(model, opt, cfg, TINY, SMOKE,
                   TrainerConfig(total_steps=8, ckpt_dir=d, ckpt_every=100,
                                 log_every=100))
    state_resumed = tr_b.run()
    assert any("restored" in e for e in tr_b.events)

    for a, b in zip(jax.tree.leaves(state_full["params"]),
                    jax.tree.leaves(state_resumed["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_checkpoint_crc_detects_corruption(tmp_path):
    cfg, model, opt = tiny_setup()
    state = init_state(model, opt, jax.random.key(0))
    path = store.save(str(tmp_path), state, step=1)
    # flip bytes in the arrays file
    arrays = os.path.join(path, "arrays.npz")
    data = bytearray(open(arrays, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(arrays, "wb").write(bytes(data))
    template = jax.eval_shape(lambda: state)
    with pytest.raises(Exception):
        store.restore(str(tmp_path), template)


def test_straggler_watchdog_detects_and_checkpoints(tmp_path):
    """Inject slow steps via the trainer clock; the watchdog must flag and
    drop a checkpoint for orchestrated restart."""
    cfg, model, opt = tiny_setup()
    steps = [(0.0, 0.1),              # step0: 0.1s
             (0.2, 0.3),              # step1: 0.1
             (0.4, 0.5),              # step2: 0.1
             (1.0, 2.0),              # step3: 1.0  (slow)
             (3.0, 4.0),              # step4: 1.0  (slow)
             (5.0, 6.0),              # step5: 1.0  (slow -> 3 strikes)
             (7.0, 7.1), (7.2, 7.3)]
    # a step reads the clock at its start, after the batch, after the
    # dispatch and at its end; dt is end minus start
    times = iter([t for a, b in steps for t in (a, a, b, b)])
    tc = TrainerConfig(total_steps=7, ckpt_dir=str(tmp_path),
                       ckpt_every=1000, log_every=100,
                       straggler_threshold=2.0)
    tr = Trainer(model, opt, cfg, TINY, SMOKE, tc,
                 _clock=lambda: next(times))
    tr.run()
    assert any(e.startswith("straggler@") for e in tr.events), tr.events
    assert store.latest_step(str(tmp_path)) is not None


def test_watchdog_unit():
    wd = StepWatchdog(threshold=2.0, patience=2)
    assert not wd.observe(0, 1.0)
    assert not wd.observe(1, 1.0)
    assert not wd.observe(2, 5.0)     # strike 1
    assert wd.observe(3, 5.0)         # strike 2 -> flagged
    assert wd.flagged_steps == [2, 3]


# ---------------------------------------------------------------------------
# elastic resharding
# ---------------------------------------------------------------------------


def test_restore_reshards_to_new_sharding(tmp_path):
    cfg, model, opt = tiny_setup()
    state = init_state(model, opt, jax.random.key(0))
    store.save(str(tmp_path), state, step=1)
    template = jax.eval_shape(lambda: state)
    dev = jax.devices()[0]
    sharding_fn = lambda key, arr: jax.sharding.SingleDeviceSharding(dev)
    restored, _ = store.restore(str(tmp_path), template,
                                sharding_fn=sharding_fn)
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


def test_engine_serves_batches():
    cfg, model, opt = tiny_setup(arch="gemma3-1b")
    params = model.init(jax.random.key(0))
    eng = Engine(model, params, cfg, SMOKE,
                 ServeConfig(max_batch=4, s_max=32))
    reqs = [Request(rid=i, prompt=np.arange(1, 6 + (i % 2)) % cfg.vocab_size,
                    max_new_tokens=4) for i in range(6)]
    out = eng.run(reqs)
    assert all(r.done for r in out)
    assert all(len(r.out_tokens) == 4 for r in out)
    # greedy decode is deterministic: same prompt -> same completion
    r1 = Request(rid=100, prompt=np.arange(1, 6), max_new_tokens=4)
    r2 = Request(rid=101, prompt=np.arange(1, 6), max_new_tokens=4)
    eng.run([r1])
    eng.run([r2])
    assert r1.out_tokens == r2.out_tokens
