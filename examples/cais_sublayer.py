"""CAIS in action: the paper's L2 sub-layer (GEMM→RS→LN→AG→GEMM) through the
graph-level dataflow optimizer, executed on an 8-virtual-device TP ring.

Shows (1) the fusion the optimizer performs, (2) numerics identical to the
barrier schedule, (3) the HLO collective census — barrier mode lowers to
all-gather/reduce-scatter phase ops, CAIS mode to collective-permute chains
interleaved with partial dots (the fine-grained overlap).

    PYTHONPATH=src python examples/cais_sublayer.py
(re-executes itself with XLA_FLAGS for 8 virtual devices)
"""
import os
import subprocess
import sys

_CHILD = "_REPRO_EXAMPLE_CHILD"


def child():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import sharding
    from repro.core import dataflow as df
    from repro.core.primitives import CAISConfig
    from repro.roofline.hlo_costs import collective_counts

    g = df.sublayer_graph()
    opt = df.optimize(g)
    print("graph:     ", " -> ".join(n.op for n in g.nodes if n.op != "input"))
    print("optimized: ", " -> ".join(n.op for n in opt.nodes
                                     if n.op != "input"))

    mesh = sharding.make_mesh((8,), ("model",))
    B, S, d, F = 2, 256, 128, 256
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (B, S, d))
    w = {"w1": jax.random.normal(ks[1], (d, F)) * 0.05,
         "scale": jax.random.normal(ks[2], (F,)) * 0.1,
         "w2": jax.random.normal(ks[3], (F, d)) * 0.05}

    def make(graph, chunks):
        def local(x, w1, scale, w2):
            return df.execute(graph, {"x": x},
                              {"w1": w1, "scale": scale, "w2": w2},
                              axis="model",
                              cais=CAISConfig(num_chunks=chunks))
        return jax.jit(sharding.shard_map(
            local, mesh=mesh,
            in_specs=(P(None, None, "model"), P("model", None), P(),
                      P(None, "model")),
            out_specs=(P(None, None, "model"),), check_vma=False))

    ref = df.execute(g, {"x": x}, w)[0]
    for name, graph in (("barrier", g), ("cais-fused", opt)):
        fn = make(graph, chunks=4)
        out = fn(x, w["w1"], w["scale"], w["w2"])[0]
        err = float(jnp.abs(out - ref).max())
        hlo = fn.lower(x, w["w1"], w["scale"], w["w2"]).compile().as_text()
        counts = collective_counts(hlo)
        census = {k: counts[k] for k in ("all-gather", "reduce-scatter",
                                         "collective-permute")}
        print(f"{name:12s} maxerr={err:.2e} hlo={census}")


def main():
    if os.environ.get(_CHILD):
        child()
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD] = "1"
    env.setdefault("PYTHONPATH", "src")
    code = ("import examples.cais_sublayer as m; m.child()"
            if os.path.exists("examples/__init__.py") else
            "import sys; sys.path.insert(0, 'examples'); "
            "import cais_sublayer; cais_sublayer.child()")
    r = subprocess.run([sys.executable, "-c", code], env=env)
    sys.exit(r.returncode)


if __name__ == "__main__":
    main()
