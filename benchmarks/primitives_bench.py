"""Wall-clock microbench of the real JAX CAIS primitives vs barrier
collectives on an 8-virtual-device ring (subprocess — the parent keeps one
device). CPU timings are NOT TPU predictions; the derived column carries the
structural evidence (HLO collective census) alongside."""
from __future__ import annotations

import os
import re
import subprocess
import sys

_CHILD = "_REPRO_PRIM_BENCH_CHILD"


def _child() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import bench_tiny, dump_rows_json, emit, time_fn
    from repro import sharding
    from repro.core import primitives as prim
    from repro.core.backends import CAISBackend, get_backend
    from repro.core.primitives import CAISConfig

    mesh = sharding.make_mesh((8,), ("model",))
    # REPRO_BENCH_TINY: CI smoke shapes — structure (HLO census) is
    # identical, only the timings shrink to seconds
    B, S, d, F = (2, 256, 128, 128) if bench_tiny() else (4, 2048, 512, 512)
    x = jax.random.normal(jax.random.key(0), (B, S, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (d, F), jnp.bfloat16)

    def census(fn, in_specs, out_specs, *args):
        txt = jax.jit(sharding.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)).lower(*args).compile().as_text()
        return {k: len(re.findall(rf"= \S+ {k}\(", txt))
                for k in ("all-gather", "reduce-scatter", "all-reduce",
                          "collective-permute")}

    cais = CAISConfig(num_chunks=4, bidirectional=True)
    cases = [
        ("ag_gemm.barrier",
         lambda a, b: prim.barrier_ag_gemm(a, b, "model"),
         (P(None, "model", None), P(None, "model")), P(None, None, "model"),
         (x, w)),
        ("ag_gemm.cais",
         lambda a, b: prim.ag_gemm(a, b, "model", cais),
         (P(None, "model", None), P(None, "model")), P(None, None, "model"),
         (x, w)),
        ("gemm_rs.barrier",
         lambda a, b: prim.barrier_gemm_rs(a, b, "model"),
         (P(None, None, "model"), P("model", None)), P(None, "model", None),
         (x, w)),
        ("gemm_rs.cais",
         lambda a, b: prim.gemm_rs(a, b, "model", cais),
         (P(None, None, "model"), P("model", None)), P(None, "model", None),
         (x, w)),
    ]
    for name, fn, ins, outs, args in cases:
        jitted = jax.jit(sharding.shard_map(fn, mesh=mesh, in_specs=ins,
                                       out_specs=outs, check_vma=False))
        us = time_fn(jitted, *args)
        c = census(fn, ins, outs, *args)
        emit(f"prim.{name}", us,
             f"hlo:ag={c['all-gather']} rs={c['reduce-scatter']} "
             f"ar={c['all-reduce']} cp={c['collective-permute']}")

    # ---- compute-aware chunk planning: planned vs fixed chunking ---------
    # The cais backend picks num_chunks per collective from payload bytes
    # and ring size (coordination.plan); compare against static chunkings.
    be = get_backend("cais")
    payload = x.size * x.dtype.itemsize   # gathered global activation bytes
    planned_c = CAISBackend.plan_chunks(payload, ring=8)
    ag_specs = ((P(None, "model", None), P(None, "model")),
                P(None, None, "model"))
    for name, cfg_c in (("planned", CAISConfig(num_chunks=None)),
                        ("fixed2", CAISConfig(num_chunks=2)),
                        ("fixed4", CAISConfig(num_chunks=4)),
                        ("fixed16", CAISConfig(num_chunks=16))):
        fn = lambda a, b, c_=cfg_c: be.ag_gemm(a, b, "model", c_)
        jitted = jax.jit(sharding.shard_map(
            fn, mesh=mesh, in_specs=ag_specs[0], out_specs=ag_specs[1],
            check_vma=False))
        us = time_fn(jitted, x, w)
        extra = f"num_chunks={planned_c} (auto)" if name == "planned" \
            else f"num_chunks={cfg_c.num_chunks}"
        emit(f"prim.ag_gemm.chunks.{name}", us, extra)

    dump_rows_json()   # CI bench-smoke artifact ($REPRO_BENCH_JSON)


def run() -> None:
    if os.environ.get(_CHILD):
        _child()
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD] = "1"
    env.setdefault("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.primitives_bench import run; run()"],
        capture_output=True, text=True, env=env, timeout=1200)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-2000:])
        raise RuntimeError("primitives bench failed")


if __name__ == "__main__":
    run()
