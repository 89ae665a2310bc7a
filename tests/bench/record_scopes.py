"""Records the trace and the compiled text that ``test_spans.py`` joins.

    python3 tests/bench/record_scopes.py tests/bench/data/period4

Over every device JAX sees (a TP ring, ``data=1 x model=n``): the gradient
of a sum of squares through ``tp.sp_period`` under ``jit``, for one
deepseek-7b block at smoke widths (MHA, d_model 64) split into two
microbatch chains with the ``cais`` backend, so the forward graph and the
custom-VJP backward graph both run. Two steps, each made of the
trainer's three host spans (``train.batch`` around taking the input,
``train.dispatch`` around the call, ``train.sync`` around waiting for it),
inside the ``bench.window`` span that ``bench.trace.Tracer`` writes.
Inputs and weights are placed on the mesh before the window, so every
op in it is the step's. Writes ``<out>.hlo.txt.gz`` (the compiled
module's text) and ``<out>.xplane.pb.gz``: the trace cut to what
``bench/spans.py`` and ``bench/trace.py`` read (the device planes' ``XLA
Ops`` and ``XLA Modules`` lines, the host's ``bench.window`` and
``train.*`` spans, no stats), still an XSpace that
``jax.profiler.ProfileData`` reads. The cut needs the XPlane schema that
the installed ``tensorflow`` package ships; it is loaded by path, without
importing ``tensorflow``. Run it on the chip."""
from __future__ import annotations

import glob
import gzip
import importlib.util
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.trace import WINDOW, Tracer  # noqa: E402

STEPS = 2


def build():
    """``(step, params, make_input)``: the jitted gradient and its
    arguments, replicated on a ``data=1 x model=n`` mesh of every
    device."""
    from jax.sharding import NamedSharding, PartitionSpec

    import repro.models.transformer as tr
    from repro import sharding
    from repro.configs import get_arch
    from repro.core import tp
    from repro.core.primitives import CAISConfig

    n = len(jax.devices())
    mesh = sharding.make_mesh((1, n), ("data", "model"))
    tpc = tp.TPContext(mesh=mesh, backend="cais", cais=CAISConfig(),
                       num_microbatches=2)
    cfg = get_arch("deepseek-7b").smoke().scaled(num_layers=1,
                                                 num_kv_heads=4)
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.device_put(
        tr.init_block(jax.random.key(0), "attn", cfg, jnp.float32), rep)

    def loss(x, p):
        out, _ = tp.sp_period(tpc, x, (p,), cfg, ("attn",),
                              norm_kind=cfg.norm)
        return jnp.sum(out * out)

    step = jax.jit(jax.grad(loss, argnums=(0, 1)))

    def make_input(i):
        rng = np.random.default_rng(i)
        return jax.device_put(
            rng.standard_normal((2, 64 * n, cfg.d_model), np.float32), rep)

    return step, params, make_input


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cut(raw: bytes) -> bytes:
    """The serialized XSpace ``raw`` cut to what the reductions read."""
    pb = _xplane_pb2()
    src, out = pb.XSpace(), pb.XSpace()
    src.ParseFromString(raw)
    for plane in src.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        meta = plane.event_metadata
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        used = set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = [ev for ev in line.events if device
                   or meta[ev.metadata_id].name == WINDOW
                   or meta[ev.metadata_id].name.startswith("train.")]
            if not evs:
                continue
            kl = kept.lines.add()
            kl.CopyFrom(line)
            del kl.events[:]
            for ev in evs:
                ke = kl.events.add()
                ke.CopyFrom(ev)
                ke.ClearField("stats")
                used.add(ev.metadata_id)
        for k in sorted(used):
            km = kept.event_metadata[k]
            km.CopyFrom(meta[k])
            km.ClearField("stats")
            km.ClearField("metadata")
    return out.SerializeToString()


def _write_gz(path: str, data: bytes) -> None:
    with open(path, "wb") as fh, \
            gzip.GzipFile(fileobj=fh, mode="wb", mtime=0) as gz:
        gz.write(data)


def main(out: str) -> None:
    step, params, make_input = build()
    xs = [make_input(i) for i in range(STEPS + 1)]
    text = step.lower(xs[0], params).compile().as_text()
    jax.block_until_ready(step(xs[0], params))
    tmp = str(ROOT / ".bench_trace" / "scopes")
    with Tracer(tmp):
        for i in range(STEPS):
            with jax.profiler.TraceAnnotation("train.batch"):
                x = xs[i + 1]
            with jax.profiler.TraceAnnotation("train.dispatch"):
                g = step(x, params)
            with jax.profiler.TraceAnnotation("train.sync"):
                jax.block_until_ready(g)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(src, "rb") as fh:
        _write_gz(out + ".xplane.pb.gz", cut(fh.read()))
    _write_gz(out + ".hlo.txt.gz", text.encode())
    shutil.rmtree(tmp, ignore_errors=True)
    for suffix in (".xplane.pb.gz", ".hlo.txt.gz"):
        print(f"wrote {out}{suffix}: {os.path.getsize(out + suffix)} bytes")
    print(f"{len(jax.devices())} devices, {jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
