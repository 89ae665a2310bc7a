"""Training launcher.

    python -m repro.launch.train --arch gemma3-1b --periods 1 --batch 1 \
        --steps 3
    python -m repro.launch.train --arch deepseek-7b --mesh host --periods 2 \
        --batch 4 --tp-mode cais --steps 3 --ckpt-dir /ckpts/run1
    python -m repro.launch.train --arch deepseek-7b --smoke --mesh debug

Widths are always the published ones; ``--periods`` cuts depth in whole
``layer_pattern`` periods and ``--batch`` sets the sequences per
data-parallel replica, so one chip or one four-chip host can run the step.
``--mesh host`` spreads tensor parallelism over every device present;
``--smoke`` is the reduced-width CPU configuration the tests use. All state
is sharded per launch/specs.py; restart is automatic from --ckpt-dir
(deterministic resume, see train/trainer.py).

``main(argv)`` and ``build(argv)`` take the argument list, so a script can
drive the same code in its own process."""
from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional, Tuple

import jax

from repro import sharding
from repro.configs import SHAPES_BY_NAME, ArchConfig, ShapeConfig, get_arch
from repro.core.backends import available_backends
from repro.data.pipeline import DataConfig
from repro.launch import specs as S
from repro.launch.cache import init_compile_cache
from repro.launch.mesh import MESHES
from repro.models import build_model
from repro.optim import cosine_schedule, make_optimizer
from repro.train import Trainer, TrainerConfig


def cut_to_size(cfg: ArchConfig, shape: ShapeConfig, *, periods: int,
                batch: int, dp: int
                ) -> Tuple[ArchConfig, ShapeConfig, List[str]]:
    """Cut depth to ``periods`` whole ``layer_pattern`` periods and the
    global batch to ``batch`` sequences per data-parallel replica (``dp``
    replicas). 0 keeps the published value. Returns the cut config and
    shape and one line per cut made."""
    cuts = []
    if periods:
        layers = periods * len(cfg.layer_pattern)
        if layers > cfg.num_layers:
            raise ValueError(f"--periods {periods} gives {layers} layers; "
                             f"{cfg.name} has {cfg.num_layers}")
        cuts.append(f"num_layers {cfg.num_layers} -> {layers} ({periods} "
                    f"period(s) of {len(cfg.layer_pattern)})")
        cfg = dataclasses.replace(cfg, num_layers=layers)
    if batch:
        cuts.append(f"global_batch {shape.global_batch} -> {batch * dp} "
                    f"({batch} per replica x {dp} replica(s))")
        shape = dataclasses.replace(shape, global_batch=batch * dp)
    return cfg, shape, cuts


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="none", choices=sorted(MESHES))
    ap.add_argument("--tp-mode", default="auto",
                    choices=available_backends())
    ap.add_argument("--periods", type=int, default=0,
                    help="depth cut: whole layer_pattern periods (0: all)")
    ap.add_argument("--batch", type=int, default=0,
                    help="sequences per data-parallel replica (0: the "
                         "shape's global batch)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shape (CPU-runnable)")
    return ap.parse_args(argv)


def build(argv: Optional[List[str]] = None):
    """The trainer the launcher runs and its initial state (restored from
    ``--ckpt-dir`` or fresh from ``--seed``), already placed on the mesh:
    ``(trainer, state)``."""
    args = parse_args(argv)
    init_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        shape = ShapeConfig("smoke_train", 128, 4, "train")
        rt = S.runtime_for(cfg, tp_mode=args.tp_mode)
        rt = dataclasses.replace(rt, compute_dtype="float32",
                                  remat=False, loss_chunk=64)
    else:
        shape = SHAPES_BY_NAME[args.shape]
        rt = S.runtime_for(cfg, tp_mode=args.tp_mode)

    mesh = MESHES[args.mesh]()
    cfg, shape, cuts = cut_to_size(cfg, shape, periods=args.periods,
                                   batch=args.batch,
                                   dp=sharding.dp_size(mesh))
    for c in cuts:
        print(f"cut: {c}")

    model = build_model(cfg, rt)
    opt = make_optimizer(cfg.optimizer,
                         cosine_schedule(args.lr, args.warmup, args.steps))
    tc = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, log_every=10,
                       microbatches=args.microbatches)
    trainer = Trainer(model, opt, cfg, shape, rt, tc, DataConfig(args.seed),
                      mesh=mesh)
    if mesh is None:
        return trainer, trainer.restore_or_init(args.seed)
    with sharding.use_mesh(mesh):
        shapes = jax.eval_shape(trainer.fresh_state, args.seed)
        sh = S.state_shardings(cfg, mesh, shapes, rt)
        return trainer, trainer.restore_or_init(args.seed, sh)


def main(argv: Optional[List[str]] = None):
    """Build and run; returns ``(trainer, final state)``."""
    trainer, state = build(argv)
    return trainer, trainer.run(state)


if __name__ == "__main__":
    main()
