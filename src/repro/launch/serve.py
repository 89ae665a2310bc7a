"""Serving launcher: load-generated requests through the paged
continuous-batching Engine (docs/serving.md).

    python -m repro.launch.serve --smoke
    python -m repro.launch.serve --arch gemma3-1b --load poisson --rate 16
    python -m repro.launch.serve --arch deepseek-7b --engine dense \
        --load burst --report reports/serve_latency.json

``--mesh host`` spreads tensor parallelism over every device present.
``main(argv)`` takes the argument list and returns ``(engine, requests)``,
so a script can drive the same code in its own process.

``--load none`` keeps the old fixed-prompt batch; ``poisson``/``burst``
drive the seeded arrival processes from :mod:`repro.serve.loadgen` and
print the p50/p99 TTFT / per-token latency / tokens-per-sec-per-device
report (optionally written as a JSON artifact via ``--report``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import List, Optional

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch import specs as S
from repro.launch.cache import init_compile_cache
from repro.launch.mesh import MESHES
from repro.models import build_model
from repro.serve import (DenseEngine, Engine, LoadSpec, Request, ServeConfig,
                         format_report, generate)


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--mesh", default="none", choices=sorted(MESHES))
    ap.add_argument("--engine", default="paged", choices=["paged", "dense"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--load", default="none",
                    choices=["none", "poisson", "burst"])
    ap.add_argument("--rate", type=float, default=16.0)
    ap.add_argument("--burst-size", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", default="")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = get_arch(args.arch)
    rt = S.runtime_for(cfg)
    if args.smoke:
        cfg = cfg.smoke()
        rt = dataclasses.replace(rt, compute_dtype="float32",
                                  remat=False)
        args.requests = min(args.requests, 4)
        args.prompt_len = min(args.prompt_len, 8)
        args.max_new = min(args.max_new, 4)
    mesh = MESHES[args.mesh]()

    model = build_model(cfg, rt)
    params = model.init(jax.random.key(0))
    extras = {}
    if cfg.is_enc_dec:
        extras["src_embed"] = np.random.default_rng(0).standard_normal(
            (args.requests, cfg.encoder.max_source_len, cfg.d_model)
        ).astype(np.float32)
    if cfg.num_prefix_tokens:
        extras["patch_embed"] = np.random.default_rng(0).standard_normal(
            (args.requests, cfg.num_prefix_tokens, cfg.vision_width)
        ).astype(np.float32)

    sc = ServeConfig(max_batch=args.requests,
                     s_max=args.prompt_len + args.max_new
                     + cfg.num_prefix_tokens)
    cls = Engine if args.engine == "paged" else DenseEngine
    eng = cls(model, params, cfg, rt, sc, mesh=mesh, extras=extras)

    if args.load == "none":
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab_size,
                                            args.prompt_len).astype(np.int32),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]
    else:
        spec = LoadSpec(kind=args.load, num_requests=args.requests,
                        rate=args.rate, burst_size=args.burst_size,
                        prompt_len_min=max(args.prompt_len // 2, 1),
                        prompt_len_max=args.prompt_len,
                        max_new_tokens=args.max_new, seed=args.seed)
        reqs = generate(spec, cfg.vocab_size)

    eng.run(reqs, key=args.seed)
    for r in reqs:
        print(f"request {r.rid}: {r.out_tokens}")
    if eng.last_report:
        print(f"[{args.engine}] {format_report(eng.last_report)}")
        if args.report:
            os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
            with open(args.report, "w") as fh:
                json.dump(eng.last_report, fh, indent=1, sort_keys=True)
            print(f"latency report -> {args.report}")
    if args.smoke:
        assert all(r.done and len(r.out_tokens) == args.max_new
                   for r in reqs), "serve smoke: incomplete requests"
        print("serve smoke OK "
              f"(arch={args.arch} engine={args.engine} paged="
              f"{getattr(eng, '_paged', False)})")
    return eng, reqs


if __name__ == "__main__":
    main()
