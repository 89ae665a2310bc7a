"""Runs one benchmark cell and prints its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell, its configuration, traffic mix, limits and metric readers are
found by name from ``BENCHMARK.json`` (see ``bench/manifest.py``); the
traffic mix's ``kind`` picks the runner in ``bench/runners/``. With
``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a profiled part of the run.
Everything runs in this one process, on the chips JAX finds; a run that
finds no accelerator of a known kind, or too few, exits with code 2 and
prints no result.

Earlier lines of standard output carry what the run noticed on the way
(compiles in the window, the engine's counters); the numbers compared for
``correct`` are the last lines of standard error and the result's last
key."""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


@dataclass
class Context:
    """What a runner is given: the cell's files, the run's arguments and
    the devices it runs on."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peaks: Dict[str, Any]
    clock: Any
    t_start: float = field(default_factory=time.monotonic)
    trace_dir: str = str(TRACE_DIR)

    @property
    def chips(self) -> int:
        return len(self.devices)

    def memory_peak(self) -> int:
        from bench.device import memory_peak_bytes

        return memory_peak_bytes(self.devices)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at one fixed path in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    kept, so only a cell's first run in a checkout compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(ctx: Context, manifest: Dict[str, Any], out: Dict[str, Any],
                correct: bool, checks: Dict[str, Any]) -> Dict[str, Any]:
    from bench import device, manifest as mf

    name = ctx.cell["name"]
    metrics: Dict[str, Any] = {}
    if ctx.trace:
        rec = dict(out["record"])
        for m in mf.metrics_of(manifest, name, "per_layer"):
            value = mf.metric_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(out["e2e"], setup_s=out["setup_s"])
        for m in mf.metrics_of(manifest, name, "end_to_end"):
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    dev = dict(device.describe(ctx.devices),
               memory_peak_bytes=out["memory_peak_bytes"])
    line: Dict[str, Any] = {"correct": correct, "attempted": out["attempted"],
                            "failed": out["failed"], "metrics": metrics,
                            "device": dev}
    tr = out["record"].get("trace")
    if ctx.trace and tr is not None:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    return line


def run_cell(ctx: Context, manifest: Dict[str, Any],
             limits: Dict[str, Any]) -> Dict[str, Any]:
    """Drive the cell and judge it; returns the result line."""
    from bench import check, manifest as mf

    out = mf.runner(ctx.traffic["kind"]).run(ctx)
    print("bench: setup_s", out["setup_s"], "notes",
          json.dumps(out["notes"], sort_keys=True), flush=True)
    correct, checks = check.verdict(out["numbers"], limits)
    return result_line(ctx, manifest, out, correct, checks)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    from bench import check, device, manifest as mf
    from bench.clock import CompileClock

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, args.workload)
    import jax

    use_compile_cache()
    try:
        peaks = device.require_chips(jax.devices(), cell["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    ctx = Context(cell=cell, config=mf.load_config(manifest, cell["config"]),
                  traffic=mf.load_traffic(cell["traffic"]), seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  devices=jax.devices()[:cell["chips"]], peaks=peaks,
                  clock=CompileClock(), t_start=T_START)
    line = run_cell(ctx, manifest, mf.load_limits(cell["name"]))
    check.print_checks(line["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
