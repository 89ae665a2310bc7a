import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import (jax locks the device count on first
# init). 512 virtual host devices host the production meshes: 16×16 single
# pod and 2×16×16 multi-pod. This module is the ONLY place that sets it.
# The dry-run compiles on the CPU backend, so it never opens (or waits
# for) an accelerator that the host may have.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import sharding                              # noqa: E402
from repro.configs import (SHAPES, SHAPES_BY_NAME, get_arch,  # noqa: E402
                           list_archs, shape_applicable)
from repro.launch import specs as S                     # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.models import build_model                    # noqa: E402
from repro.roofline.hlo_analyzer import analyze      # noqa: E402
from repro.roofline.hlo_costs import (collective_bytes,  # noqa: E402
                                      cost_summary, memory_summary,
                                      roofline_terms)
from repro.runtime import Runtime                       # noqa: E402


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               tp_mode: str = "auto", cais_chunks: "int | None" = None,
               rt_overrides: dict = None):
    """Lower + compile one (arch × shape × mesh) cell. Returns (lowered,
    compiled, meta). ``rt_overrides`` patches Runtime fields (the §Perf
    hillclimb uses this to try remat/SP/chunking variants)."""
    import dataclasses

    cfg = get_arch(arch_name)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rt = S.runtime_for(cfg, tp_mode=tp_mode, cais_chunks=cais_chunks)
    if rt_overrides:
        from repro.runtime import TPConfig
        ov = dict(rt_overrides)
        tp_fields = {f.name for f in dataclasses.fields(TPConfig)}
        tp_ov = {k: ov.pop(k) for k in list(ov) if k in tp_fields}
        tp = dataclasses.replace(rt.tp, **tp_ov) if tp_ov else rt.tp
        rt = dataclasses.replace(rt, tp=tp, **ov)
    model = build_model(cfg, rt)
    ins = S.input_specs(cfg, shape, rt, model=model)

    with sharding.use_mesh(mesh):
        if shape.kind == "train":
            from repro.optim import constant_schedule, make_optimizer
            from repro.train.step import make_train_step
            opt = make_optimizer(cfg.optimizer, constant_schedule(1e-4))
            # gradient accumulation bounds activation temps for the huge
            # MoE archs (per-device batch stays >= 1 on both meshes)
            micro = 4 if cfg.param_count() > 4e10 else 1
            step = make_train_step(model, opt, rt, microbatches=micro)
            st_sh = S.state_shardings(cfg, mesh, ins["state"], rt,
                                      fsdp=rt.param_dtype == "bfloat16")
            b_sh = S.batch_shardings(cfg, shape, mesh, rt)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(ins["state"], ins["batch"])
        elif shape.kind == "prefill":
            p_sh = S.param_shardings(cfg, mesh, ins["params"],
                                     fsdp=rt.param_dtype == "bfloat16")
            b_sh = S.batch_shardings(cfg, shape, mesh, rt)
            fn = lambda p, b: model.prefill(p, b, s_max=shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(ins["params"], ins["batch"])
        else:  # decode
            p_sh = S.param_shardings(cfg, mesh, ins["params"],
                                     fsdp=rt.param_dtype == "bfloat16")
            c_sh = S.cache_shardings(mesh, ins["caches"], rt.cache_layout)
            t_sh = sharding.named_sharding(mesh, *S.sanitize_spec(
                mesh, (S.B_AX, None), ins["token"].shape))
            i_sh = sharding.named_sharding(mesh, *S.sanitize_spec(
                mesh, (S.B_AX,), ins["idx"].shape))
            jitted = jax.jit(model.decode_step,
                             in_shardings=(p_sh, t_sh, c_sh, i_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(ins["params"], ins["token"],
                                   ins["caches"], ins["idx"])

        compiled = lowered.compile()

    return lowered, compiled, {"mesh": "multi" if multi_pod else "single",
                               "tp_mode": tp_mode}


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             tp_mode: str = "auto", cais_chunks: "int | None" = None,
             verbose: bool = True, rt_overrides: dict = None) -> dict:
    t0 = time.monotonic()
    n_chips = 512 if multi_pod else 256
    rec = {"arch": arch_name, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "chips": n_chips, "tp_mode": tp_mode,
           "rt_overrides": rt_overrides or {}}
    try:
        lowered, compiled, meta = lower_cell(arch_name, shape_name,
                                             multi_pod, tp_mode, cais_chunks,
                                             rt_overrides)
        if lowered is None:
            rec["status"] = "skipped"
            rec["reason"] = meta["skipped"]
            return rec
        rec["status"] = "ok"
        hlo = compiled.as_text()
        rec["cost"] = cost_summary(compiled)       # raw (scan bodies ×1)
        rec["memory"] = memory_summary(compiled)
        rec["collectives"] = collective_bytes(hlo)  # raw, unmultiplied
        # while-aware analysis: scan bodies × trip count (the real costs)
        rec["hlo_analysis"] = analyze(hlo)
        # collective term uses per-direction wire bytes: bidirectional
        # permute schedules occupy both full-duplex ICI directions at once
        roof = roofline_terms(rec["hlo_analysis"]["flops"],
                              rec["hlo_analysis"]["bytes"],
                              rec["hlo_analysis"].get(
                                  "collective_wire",
                                  rec["hlo_analysis"]["collective_total"]))
        rec["roofline"] = roof.as_dict()
        cfg = get_arch(arch_name)
        rec["params"] = cfg.param_count()
        rec["active_params"] = cfg.active_param_count()
        if verbose:
            print(f"  memory_analysis: {compiled.memory_analysis()}")
            print(f"  cost_analysis: flops={rec['cost']['flops']:.3e} "
                  f"bytes={rec['cost']['bytes']:.3e}")
            print(f"  collective bytes/device: {rec['collectives']}")
    except Exception as e:  # a failure here is a bug in our system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["compile_s"] = round(time.monotonic() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    from repro.core.backends import available_backends
    ap.add_argument("--tp-mode", default="auto",
                    choices=available_backends())
    ap.add_argument("--cais-chunks", type=int, default=None,
                    help="static ring-chunk override; default lets the cais "
                         "backend plan per collective")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}.{shape}.{'multi' if multi else 'single'}" + \
                    (f".{args.tp_mode}" if args.tp_mode != "auto" else "")
                print(f"=== {tag} ===", flush=True)
                rec = run_cell(arch, shape, multi, args.tp_mode,
                               args.cais_chunks)
                print(f"  -> {rec['status']} ({rec.get('compile_s', 0)}s)"
                      + (f" {rec.get('reason', rec.get('error', ''))}"
                         if rec["status"] != "ok" else ""), flush=True)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "error":
                    failures += 1
    print(f"dry-run complete; {failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
