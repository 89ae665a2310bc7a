"""§Perf hillclimbing driver: run Runtime variants of a dry-run cell and
log hypothesis → change → before/after roofline terms (EXPERIMENTS.md §Perf).

    PYTHONPATH=src python -m repro.launch.hillclimb --cell deepseek-7b:train_4k

Each variant is one (hypothesis, Runtime patch); the dominant term of the
baseline decides which levers are enumerated (DESIGN.md §4 + the assignment's
per-iteration methodology). After the sweep a ranking table (ordered by the
dominant roofline bound) is printed and written to ``<out>/summary.json``.

``--auto`` replaces the hand-written VARIANTS ladder with the repro.plan
search: it sweeps the (backend × num_chunks × microbatch-split) grid of a
2-block dense period proxy of the cell, ranks the grid by simulated makespan,
then dry-runs only the ``--top`` best so the simulated ranking can be checked
against the measured roofline bounds (docs/planner.md)."""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json      # noqa: E402

from repro.launch.dryrun import run_cell  # noqa: E402

# (name, hypothesis, tp_mode, cais_chunks, rt_overrides)
VARIANTS = {
    "baseline": ("paper-faithful SP-TP with monolithic (NVLS-style) "
                 "collectives scheduled by XLA", "auto", 8, {}),
    "barrier": ("explicit barrier collectives (strict NVLS phase structure; "
                "expect ≥ baseline collective exposure)", "barrier", 8, {}),
    "cais8": ("CAIS decomposed bidirectional ring schedules, 8 chunks: "
              "collective bytes move to collective-permute and overlap "
              "with partial GEMMs", "cais", 8, {}),
    "cais-plan": ("compute-aware chunking: the cais backend picks "
                  "num_chunks per collective from payload bytes and ring "
                  "size (coordination.plan) instead of one static value",
                  "cais", None, {}),
    "cais2": ("coarser chunks (2): fewer permutes, bigger staging buffer — "
              "latency ↓, overlap granularity ↓", "cais", 2, {}),
    "cais16": ("finer chunks (16): finer overlap, more per-hop latency",
               "cais", 16, {}),
    "cais8-uni": ("unidirectional rings (CAIS-Base analogue): one ICI "
                  "direction idles — collective term should ~2×",
                  "cais", 8, {"bidirectional": False}),
    "no-remat": ("disable activation checkpointing: recompute flops "
                 "disappear (compute term ↓ ~25%), memory residency ↑",
                 "auto", 8, {"remat": False}),
    "no-sp": ("disable sequence parallelism: activations replicated on "
              "model axis between blocks — collective pattern shifts "
              "AG/RS → AR", "auto", 8, {"sequence_parallel": False}),
    # ---- decode-cell levers ----
    "cache-repl": ("replicate the KV cache over the TP axis instead of "
                   "context-parallel sharding: memory term should blow up "
                   "~tp x on the cache-read side (negative control)",
                   "auto", 8, {"cache_layout": "batch_only"}),
    "f32-compute": ("f32 activations/caches instead of bf16: memory term "
                    "x2 (confirms the dtype lever)", "auto", 8,
                    {"compute_dtype": "float32"}),
    # ---- stacked winners ----
    "cais2-noremat": ("stack the two confirmed wins: coarse-chunk CAIS "
                      "rings + no recompute (activations fit at 4k)",
                      "cais", 2, {"remat": False}),
}

# production model-axis degree (launch.mesh: 16×16 / 2×16×16, model=16)
_TP = 16


def _dense_weight_shapes(d: int, d_ff: int, blocks: int,
                         has_gate: bool) -> dict:
    """Weight-key → global shape map for ``dense_period_graph`` blocks
    (mirrors ``tp._dense_block_nodes`` naming)."""
    out = {}
    for i in range(blocks):
        p = f"b{i}."
        out.update({p + "scale1": (d,), p + "scale2": (d,),
                    p + "wq": (d, d), p + "wk": (d, d), p + "wv": (d, d),
                    p + "wo": (d, d), p + "w_up": (d, d_ff),
                    p + "w_down": (d_ff, d)})
        if has_gate:
            out[p + "w_gate"] = (d, d_ff)
    return out


def auto_variants(arch_name: str, shape_name: str, multi_pod: bool,
                  top_k: int = 3):
    """Planner-driven variant enumeration: sweep the (backend × chunks ×
    microbatch) grid of the cell's 2-block dense period proxy by simulated
    makespan, return the ``top_k`` grid points as hillclimb variants plus
    the full simulated ranking ``[{variant, makespan_s, ...}, ...]``."""
    from repro import plan as plan_mod
    from repro.configs import SHAPES_BY_NAME, get_arch
    from repro.core import dataflow as df, tp as tp_mod
    from repro.hw import V5E

    cfg = get_arch(arch_name)
    shape = SHAPES_BY_NAME[shape_name]
    chips = 512 if multi_pod else 256
    dp = max(chips // _TP, 1)
    b_loc = max(shape.global_batch // dp, 1)
    seq = 1 if shape.kind == "decode" else shape.seq_len
    has_gate = cfg.act != "gelu_mlp"

    core = lambda q, k, v: q  # opaque for the cost model   # noqa: E731
    base = tp_mod.dense_period_graph([core] * 2, has_gate=has_gate,
                                     act=cfg.act)
    weights = _dense_weight_shapes(cfg.d_model, cfg.d_ff, blocks=2,
                                   has_gate=has_gate)
    fabric = plan_mod.fabric_from_hw(V5E, _TP)

    grid = []
    for backend in ("barrier", "cais"):
        chunk_grid = (None,) if backend == "barrier" else (None, 2, 8, 16)
        for mb in (1, 2, 4):
            if b_loc % mb or mb > b_loc:
                continue
            merged = base if mb == 1 else df.merge_graphs(
                [base] * mb, share_weights=True)
            g2 = df.fuse_sublayer_chain(df.fuse_shared_gather(
                df.fuse_compute_aware(merged)))
            values = plan_mod.microbatch_value_shapes(
                (b_loc, seq, cfg.d_model), mb)
            for chunks in chunk_grid:
                p = plan_mod.search_pairing(
                    g2, fabric=fabric, backend=backend,
                    value_shapes=values, weight_shapes=weights,
                    dtype_bytes=2, num_microbatches=mb,
                    chunk_candidates=(chunks,))
                cname = "cplan" if chunks is None else f"c{chunks}"
                grid.append({"variant": f"{backend}-{cname}-mb{mb}",
                             "backend": backend, "chunks": chunks,
                             "microbatches": mb,
                             "makespan_s": p.makespan})
    grid.sort(key=lambda r: r["makespan_s"])
    for rank, row in enumerate(grid, 1):
        row["sim_rank"] = rank

    variants = {}
    for row in grid[:top_k]:
        hyp = (f"planner pick #{row['sim_rank']}: simulated makespan "
               f"{row['makespan_s']:.3e}s for backend={row['backend']} "
               f"chunks={row['chunks']} microbatches={row['microbatches']} "
               f"on the 2-block dense period proxy")
        variants[row["variant"]] = (hyp, row["backend"], row["chunks"],
                                    {"microbatches": row["microbatches"]})
    return variants, grid


def summarize(results: dict, cell: str, mesh: str, out_dir: str,
              sim_ranking=None) -> dict:
    """Rank ok variants by their dominant roofline bound, print the table,
    name the winner, and persist everything to ``<out_dir>/summary.json``."""
    ok, failed = [], []
    for name, rec in results.items():
        if rec["status"] != "ok":
            failed.append({"variant": name, "status": rec["status"]})
            continue
        r = rec["roofline"]
        ok.append({"variant": name, "status": "ok",
                   "dominant": r["dominant"],
                   "bound_s": max(r["compute_s"], r["memory_s"],
                                  r["collective_s"]),
                   "compute_s": r["compute_s"], "memory_s": r["memory_s"],
                   "collective_s": r["collective_s"],
                   "hypothesis": rec.get("hypothesis", "")})
    ok.sort(key=lambda r: r["bound_s"])
    winner = ok[0]["variant"] if ok else None
    summary = {"cell": cell, "mesh": mesh, "winner": winner,
               "ranked": ok + failed}
    if sim_ranking is not None:
        summary["simulated_ranking"] = sim_ranking

    print("\n=== ranking (dominant roofline bound, best first) ===")
    print(f"{'rank':>4} {'variant':<18} {'bound_s':>10} {'dominant':<10} "
          f"{'compute':>10} {'memory':>10} {'collective':>10}")
    for i, r in enumerate(ok, 1):
        print(f"{i:>4} {r['variant']:<18} {r['bound_s']:>10.3e} "
              f"{r['dominant']:<10} {r['compute_s']:>10.3e} "
              f"{r['memory_s']:>10.3e} {r['collective_s']:>10.3e}")
    for r in failed:
        print(f"   - {r['variant']:<18} {r['status']}")
    if winner:
        print(f"winner: {winner} ({ok[0]['dominant']}-bound, "
              f"{ok[0]['bound_s']:.3e}s)")

    if sim_ranking is not None and ok:
        measured_rank = {r["variant"]: i for i, r in enumerate(ok, 1)}
        print("\n=== simulated vs measured (dry-run subset) ===")
        print(f"{'variant':<18} {'sim_rank':>8} {'sim_s':>10} "
              f"{'meas_rank':>9} {'bound_s':>10}")
        for row in sim_ranking:
            if row["variant"] not in measured_rank:
                continue
            m = next(r for r in ok if r["variant"] == row["variant"])
            print(f"{row['variant']:<18} {row['sim_rank']:>8} "
                  f"{row['makespan_s']:>10.3e} "
                  f"{measured_rank[row['variant']]:>9} "
                  f"{m['bound_s']:>10.3e}")

    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(f"summary -> {os.path.join(out_dir, 'summary.json')}")
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    help="arch:shape, e.g. deepseek-7b:train_4k")
    ap.add_argument("--variants", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="reports/hillclimb")
    ap.add_argument("--auto", action="store_true",
                    help="enumerate variants with the repro.plan search "
                         "instead of the hand-written VARIANTS ladder")
    ap.add_argument("--top", type=int, default=3,
                    help="--auto: dry-run this many best simulated points")
    args = ap.parse_args()

    arch, shape = args.cell.split(":")
    os.makedirs(args.out, exist_ok=True)

    sim_ranking = None
    if args.auto:
        variants, sim_ranking = auto_variants(arch, shape,
                                              args.mesh == "multi", args.top)
        print(f"=== planner grid: {len(sim_ranking)} points, "
              f"dry-running top {len(variants)} ===")
        for row in sim_ranking:
            print(f"  #{row['sim_rank']:<3} {row['variant']:<18} "
                  f"simulated={row['makespan_s']:.3e}s", flush=True)
    else:
        names = list(VARIANTS) if args.variants == "all" \
            else args.variants.split(",")
        variants = {n: VARIANTS[n] for n in names}

    results = {}
    for name, (hyp, mode, chunks, rto) in variants.items():
        print(f"=== {arch}:{shape} [{name}] ===\n  hypothesis: {hyp}",
              flush=True)
        rec = run_cell(arch, shape, args.mesh == "multi", mode, chunks,
                       verbose=False, rt_overrides=rto)
        rec["variant"] = name
        rec["hypothesis"] = hyp
        results[name] = rec
        if rec["status"] == "ok":
            r = rec["roofline"]
            print(f"  compute={r['compute_s']:.3e}s "
                  f"memory={r['memory_s']:.3e}s "
                  f"collective={r['collective_s']:.3e}s "
                  f"dominant={r['dominant']} ({rec['compile_s']}s compile)",
                  flush=True)
            ca = rec["hlo_analysis"]
            print(f"  coll mix: " + " ".join(
                f"{k.split('_')[1]}={v:.2e}" for k, v in ca.items()
                if k.startswith("coll_") and v > 0), flush=True)
        else:
            print(f"  -> {rec['status']}: {rec.get('error', '')[:200]}",
                  flush=True)
        with open(os.path.join(args.out, f"{arch}.{shape}.{name}.json"),
                  "w") as f:
            json.dump(rec, f, indent=1)

    summarize(results, args.cell, args.mesh, args.out, sim_ranking)


if __name__ == "__main__":
    main()
