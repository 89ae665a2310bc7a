"""Serving bench cells (docs/serving.md): paged-KV continuous batching vs
the dense static-batch engine under a seeded burst arrival process, on an
8-virtual-device TP ring (subprocess — the parent keeps one device), for
both collective backends.

Rows:
- ``serve.paged_vs_dense.{barrier,cais}`` — paged-engine makespan (µs) with
  the dense makespan and speedup in ``derived``. The burst process is the
  adversarial case for static batching: a same-length prompt group spans
  bursts, so the dense engine stalls until its LAST member arrives while
  the paged engine admits and chunk-prefills work as it lands.
- ``serve.latency.{mode}`` — p50 TTFT (µs) with p99 TTFT, p50/p99
  per-token latency, tokens/sec/device and peak KV-block utilization in
  ``derived``.

Both engines are warmed first (same request shapes, arrivals zeroed) so the
timed runs compare steady-state serving, not jit compiles. Greedy outputs
are asserted token-identical between the engines before timing. The paged
engine runs ``TPConfig(planner="perfsim")`` — serve-period graphs go
through the plan cache under reports/plans/ like the training cells. With
``$REPRO_BENCH_JSON`` set the rows are APPENDED to any rows already in the
file (the sublayer bench writes first in CI), and the full latency reports
are written to ``$REPRO_SERVE_REPORT`` (default ``serve-latency.json``)
as the uploaded artifact. Wall-clock on CPU-emulated devices is
informational; the row schema, parity and makespan ordering are the
contract."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import dump_rows_json, emit, record

_CHILD = "_REPRO_SERVE_BENCH_CHILD"


def _serve_child() -> None:
    import jax

    from benchmarks.common import bench_tiny
    from repro import sharding
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.runtime import Runtime, TPConfig
    from repro.serve import (DenseEngine, Engine, LoadSpec, ServeConfig,
                             generate)

    mesh = sharding.make_mesh((1, 8), ("data", "model"))
    n_req, max_new, gap = (8, 4, 0.1) if bench_tiny() else (16, 8, 0.25)
    cfg = get_arch("deepseek-7b").smoke().scaled(
        num_layers=2, d_model=128, num_heads=8, num_kv_heads=8, head_dim=16,
        d_ff=256)
    spec = LoadSpec(kind="burst", num_requests=n_req, burst_size=4,
                    gap_s=gap, prompt_len_min=4, prompt_len_max=12,
                    max_new_tokens=max_new, seed=0)
    sc = ServeConfig(max_batch=4, s_max=32, block_size=4, prefill_chunk=8)
    reports = {}
    for mode in ("barrier", "cais"):
        rt = Runtime(compute_dtype="float32", remat=False, loss_chunk=16,
                     tp=TPConfig(mode=mode, chunks=2, planner="perfsim"))
        model = build_model(cfg, rt)
        params = model.init(jax.random.key(0))
        pag = Engine(model, params, cfg, rt, sc, mesh=mesh)
        den = DenseEngine(model, params, cfg, rt, sc, mesh=mesh)
        assert pag._paged, "bench arch must take the paged path"

        def arrived_now(reqs):
            for r in reqs:
                r.arrival_time = 0.0
            return reqs

        # warm both engines (compiles the decode-only and mixed step shapes
        # / the per-length dense prefills), then assert greedy parity
        warm_p = pag.run(arrived_now(generate(spec, cfg.vocab_size)))
        warm_d = den.run(arrived_now(generate(spec, cfg.vocab_size)))
        assert [r.out_tokens for r in warm_p] == \
            [r.out_tokens for r in warm_d], f"greedy parity broken ({mode})"

        pag.run(generate(spec, cfg.vocab_size))
        t_paged = pag.last_report["makespan_s"]
        den.run(generate(spec, cfg.vocab_size))
        t_dense = den.last_report["makespan_s"]
        emit(f"serve.paged_vs_dense.{mode}", t_paged * 1e6,
             f"dense_us={t_dense * 1e6:.0f} "
             f"speedup={t_dense / t_paged:.2f}x burst={spec.burst_size}"
             f"x{n_req // spec.burst_size}")
        rep = pag.last_report
        emit(f"serve.latency.{mode}", rep["ttft_p50_ms"] * 1e3,
             f"ttft_p99_ms={rep['ttft_p99_ms']:.2f} "
             f"per_token_p50_ms={rep['per_token_p50_ms']:.2f} "
             f"per_token_p99_ms={rep['per_token_p99_ms']:.2f} "
             f"toks_per_s_per_dev={rep['tokens_per_sec_per_device']:.1f} "
             f"kv_util={rep['kv_block_utilization']:.2f} "
             f"prefix_hits={rep['prefix_hits']:.0f}")
        reports[f"paged.{mode}"] = pag.last_report
        reports[f"dense.{mode}"] = den.last_report
    out = os.environ.get("_REPRO_SERVE_REPORT_TMP")
    if out:
        with open(out, "w") as fh:
            json.dump(reports, fh, indent=1, sort_keys=True)


def run() -> None:
    if os.environ.get(_CHILD):
        _serve_child()
        dump_rows_json()        # child rows → the path the parent hands us
        return
    # append mode: keep whatever rows an earlier bench already put in the
    # committed JSON (CI runs sublayer first), then add the serve cells
    base = os.environ.get("REPRO_BENCH_JSON")
    if base and os.path.exists(base):
        with open(base) as fh:
            for row in json.load(fh):
                record(row["name"], row["us_per_call"], row["derived"])
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD] = "1"
    env.setdefault("PYTHONPATH", "src")
    report_path = os.environ.get("REPRO_SERVE_REPORT", "serve-latency.json")
    with tempfile.TemporaryDirectory() as td:
        env["REPRO_BENCH_JSON"] = os.path.join(td, "child-rows.json")
        env["_REPRO_SERVE_REPORT_TMP"] = os.path.join(td, "reports.json")
        out = subprocess.run(
            [sys.executable, "-c",
             "from benchmarks.serve_bench import run; run()"],
            capture_output=True, text=True, env=env, timeout=1800)
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            sys.stderr.write(out.stderr[-2000:])
            raise RuntimeError("serve bench failed")
        with open(env["REPRO_BENCH_JSON"]) as fh:
            for row in json.load(fh):
                record(row["name"], row["us_per_call"], row["derived"])
        with open(env["_REPRO_SERVE_REPORT_TMP"]) as fh:
            reports = json.load(fh)
    with open(report_path, "w") as fh:
        json.dump(reports, fh, indent=1, sort_keys=True)
    print(f"latency reports -> {report_path}")

    import jax

    from benchmarks.common import bench_tiny
    emit("meta.serve_env", 0.0,
         f"tiny={int(bench_tiny())} jax={jax.__version__} "
         f"platform={jax.default_backend()} "
         "note=cpu-emulated-makespans-informational")
    dump_rows_json()


if __name__ == "__main__":
    run()
