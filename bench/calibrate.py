"""Readings that the limits of ``limits/<cell>.json`` are set from, taken
on the chip at the cell's own size. Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        --control-seeds 1 2 3 [--seconds 10] [--out file.jsonl]

For every seed it reads the numbers that decide ``correct`` for the
program (the lower readings). For the control seeds it also reads them
for the control, the reference computed in float8 in the program's place,
and for the faults the cell can have, planted in the reference put in the
program's place: half of each batch's tokens left out of the loss
(training), one served token altered (serving). A state left unchanged
reads 1 by construction and needs no run. On several chips,
``--no-exchange-seeds`` also reads the program with the exchange between
chips left out. One process reads every seed, so set-up is paid once."""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as bench_run  # noqa: F401  (puts the checkout on sys.path)

import jax
import numpy as np

from bench import check, device, manifest as mf, traffic, weights
from bench.clock import CompileClock
from bench.reference import serve as ref_serve, train as ref_train


def calibrate_train(cfg, job, seeds, control_seeds, emit):
    from repro.data.pipeline import DataConfig

    runner = mf.runner("train")
    trainer, state, layout, key, shardings = runner.build(cfg, job,
                                                          seeds[0])
    for i, seed in enumerate(seeds):
        if i:
            key = weights.seed_key(seed)
            state = runner.fresh_state(trainer, layout, shardings, key)
            trainer.history.clear()
            trainer.data = DataConfig(seed=seed, zipf_alpha=job["zipf_alpha"])
        state, prog = runner.program_readings(trainer, state, layout, key,
                                           shardings, cfg, job)
        del state
        gc.collect()
        steps = job["checked_steps"]
        ref = ref_train.readings(cfg, job, seed, layout, steps)
        emit({"seed": seed, "who": "program",
              "numbers": check.train_numbers(prog, ref),
              "losses": prog["loss"], "ref_losses": ref["loss"]})
        if seed in control_seeds:
            ctl = ref_train.readings(cfg, job, seed, layout, steps, quant=True)
            emit({"seed": seed, "who": "control",
                  "numbers": check.train_numbers(ctl, ref)})
            half = ref_train.readings(cfg, job, seed, layout, steps,
                                      drop_half=True)
            emit({"seed": seed, "who": "fault:half_batch",
                  "numbers": check.train_numbers(half, ref)})


def calibrate_no_exchange(cfg, job, seeds, emit):
    """The program with the exchange between chips left out: every ring
    permute returns what it was sent. Built after the sound readings, so
    its step is traced and compiled anew."""
    import jax as jax_mod

    runner = mf.runner("train")
    jax_mod.lax.ppermute = lambda x, axis_name, perm: x
    for seed in seeds:
        trainer, state, layout, key, shardings = runner.build(cfg, job,
                                                              seed)
        state, prog = runner.program_readings(trainer, state, layout, key,
                                           shardings, cfg, job)
        del state, trainer
        gc.collect()
        ref = ref_train.readings(cfg, job, seed, layout,
                                 job["checked_steps"])
        emit({"seed": seed, "who": "fault:no_exchange",
              "numbers": check.train_numbers(prog, ref),
              "losses": prog["loss"], "ref_losses": ref["loss"]})


def calibrate_serve(cfg, tr, seeds, control_seeds, seconds, emit):
    runner = mf.runner("serve")
    vocab = cfg["vocab_size"]
    engine, layout = runner.build(cfg, seeds[0])
    runner.warm_up(engine, vocab)
    s_max = cfg["program"]["serve"]["s_max"]
    rows = runner.check_rows(tr)
    make = jax.jit(lambda k: weights.make_params(layout, k))
    for seed in seeds:
        if engine.params is None:
            engine.params = make(weights.seed_key(seed))
        reqs = runner.requests_of(traffic.serve_requests(tr, seed, seconds,
                                                      vocab))
        engine.run(reqs, key=0)
        picked = runner.sample(reqs, tr["check_requests"], seed)
        served = [(np.asarray(r.prompt, np.int32), list(r.out_tokens))
                  for r in picked]
        params, engine.params = engine.params, None
        del params
        gc.collect()
        ctl = seed in control_seeds
        ref = ref_serve.gaps(cfg, seed, layout, served, s_max, rows,
                             control=ctl)
        emit({"seed": seed, "who": "program", "numbers": {"gap": ref["gap"]},
              "tokens": ref["tokens"], "requests": len(reqs),
              "failed": sum(not r.done for r in reqs)})
        if ctl:
            emit({"seed": seed, "who": "control",
                  "numbers": {"gap": ref["control_gap"]}})
            prompt, out = served[0]
            j = len(out) // 2
            out = list(out)
            out[j] = (out[j] + 1) % vocab
            bad = ref_serve.gaps(cfg, seed, layout, [(prompt, out)], s_max,
                                 rows)
            emit({"seed": seed, "who": "fault:token_altered",
                  "numbers": {"gap": bad["gap"]}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--no-exchange-seeds", type=int, nargs="*", default=[],
                    help="training on several chips: also read the program "
                         "with the exchange between chips left out")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    manifest = mf.load_manifest()
    cell = mf.cell(manifest, args.workload)
    bench_run.use_compile_cache()
    device.require_chips(jax.devices(), cell["chips"])
    CompileClock()
    cfg = mf.load_config(manifest, cell["config"])
    tr = mf.load_traffic(cell["traffic"])
    out = open(args.out, "a") if args.out else None
    t0 = time.monotonic()

    def emit(rec):
        rec = dict(rec, cell=cell["name"], t=time.monotonic() - t0)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        if tr["kind"] == "train":
            calibrate_train(cfg, tr, args.seeds, set(args.control_seeds),
                            emit)
            if args.no_exchange_seeds:
                calibrate_no_exchange(cfg, tr, args.no_exchange_seeds, emit)
        else:
            calibrate_serve(cfg, tr, args.seeds, set(args.control_seeds),
                            args.seconds, emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
