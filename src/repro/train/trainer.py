"""Trainer: the production step loop — checkpoint/restart, straggler
watchdog, heartbeat, preemption handling, deterministic resume.

Restart semantics: the data pipeline is keyed on (seed, step), so
restore(step) + iterate(start_step=step) replays the exact stream; loss
curves across a kill/restart are bitwise-continuable (tested in
tests/test_substrate.py::test_checkpoint_restart_determinism).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro import sharding
from repro.checkpoint import store
from repro.configs.base import ArchConfig, ShapeConfig
from repro.data.pipeline import DataConfig, make_batch
from repro.ft.watchdog import Heartbeat, PreemptionGuard, StepWatchdog
from repro.optim.optimizers import Optimizer
from repro.runtime import Runtime
from repro.train.step import init_state, make_train_step


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    microbatches: int = 1
    straggler_threshold: float = 2.5
    heartbeat_path: Optional[str] = None


@dataclass
class Trainer:
    model: Any
    opt: Optimizer
    arch: ArchConfig
    shape: ShapeConfig
    rt: Runtime = Runtime()
    cfg: TrainerConfig = field(default_factory=TrainerConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: Any = None
    # injectable for tests: step-time override to simulate stragglers
    _clock: Callable[[], float] = time.monotonic

    def __post_init__(self):
        # the step donates the state it replaces, so a device holds one
        # state at a time (run() never reads a state after stepping it)
        self.train_step = jax.jit(
            make_train_step(self.model, self.opt, self.rt,
                            self.cfg.microbatches), donate_argnums=(0,))
        self.watchdog = StepWatchdog(threshold=self.cfg.straggler_threshold)
        self.history: List[Dict[str, float]] = []
        self.events: List[str] = []

    # ----- state -----
    def fresh_state(self, seed: int = 0, shardings=None):
        """Initial state from ``seed``, built on the device. ``shardings``
        (a pytree of shardings matching the state) places every array
        where it belongs as it is made, so no device ever holds the whole
        state of a sharded model."""
        init = jax.jit(lambda key: init_state(self.model, self.opt, key),
                       out_shardings=shardings)
        return init(jax.random.key(seed))

    def restore_or_init(self, seed: int = 0, shardings=None):
        if self.cfg.ckpt_dir and store.latest_step(self.cfg.ckpt_dir) is not None:
            template = jax.eval_shape(self.fresh_state, seed)
            state, manifest = store.restore(self.cfg.ckpt_dir, template)
            self.events.append(f"restored step {manifest['step']}")
            return state if shardings is None else \
                jax.device_put(state, shardings)
        return self.fresh_state(seed, shardings)

    # ----- loop -----
    def run(self, state=None, seed: int = 0):
        state = state if state is not None else self.restore_or_init(seed)
        hb = None
        if self.cfg.heartbeat_path:
            hb = Heartbeat(self.cfg.heartbeat_path)
            hb.start()
        saver = store.AsyncSaver(self.cfg.ckpt_dir) if self.cfg.ckpt_dir \
            else None
        try:
            with PreemptionGuard() as guard, \
                    sharding.use_mesh(self.mesh):
                start = int(state["step"])
                for step in range(start, self.cfg.total_steps):
                    # three host phases that do not overlap, each a
                    # profiler span (recorded only while a trace runs) and
                    # a time in the history: make the batch, dispatch the
                    # step, wait for its loss
                    t0 = self._clock()
                    with jax.profiler.TraceAnnotation("train.batch"):
                        batch = make_batch(self.arch, self.shape, step,
                                           self.data)
                    t1 = self._clock()
                    with jax.profiler.TraceAnnotation("train.dispatch"):
                        state, metrics = self.train_step(state, batch)
                    t2 = self._clock()
                    with jax.profiler.TraceAnnotation("train.sync"):
                        loss = float(metrics["loss"])
                    t3 = self._clock()
                    dt = t3 - t0

                    if self.watchdog.observe(step, dt):
                        self.events.append(f"straggler@{step}")
                        # policy: checkpoint now so an orchestrator can
                        # restart on healthy hosts without losing work
                        if saver:
                            saver.save(state, step + 1)
                        self.watchdog.reset()

                    self.history.append(
                        {"step": step, "loss": loss, "dt": dt,
                         "batch_s": t1 - t0, "dispatch_s": t2 - t1,
                         "sync_s": t3 - t2})
                    if step % self.cfg.log_every == 0:
                        print(f"step {step:6d} loss {loss:8.4f} "
                              f"dt {dt*1e3:7.1f}ms")
                    if saver and (step + 1) % self.cfg.ckpt_every == 0:
                        saver.save(state, step + 1)
                    if guard.requested:
                        self.events.append(f"preempted@{step}")
                        if saver:
                            saver.save(state, step + 1)
                        break
        finally:
            if saver:
                saver.wait()
            if hb:
                hb.stop()
        return state
