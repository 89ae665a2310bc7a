"""Records the small trace that ``test_trace.py`` reads.

    python3 tests/bench/record_fixture.py tests/bench/data/ring4.xplane.pb

On every device JAX sees: three steps, each a bf16 matmul followed by an
all-reduce and a ring permute over the devices (shard_map), each step
under a ``bench.step`` host span and followed by 20 ms of host sleep under
a ``bench.sleep`` span, all inside the ``bench.window`` span that
``bench.trace.Tracer`` writes. Run it on the chip; the trace it writes is
what the test holds the reduction to."""
from __future__ import annotations

import glob
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from bench.trace import Tracer  # noqa: E402

N = 2048
SLEEP_S = 0.02


def main(out: str) -> None:
    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))

    def body(a):
        b = a[0] @ a[0]
        b = jax.lax.psum(b, "x")
        b = jax.lax.ppermute(b, "x", [(i, (i + 1) % n) for i in range(n)])
        return (b / n)[None]

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x")))
    x = jax.device_put(jnp.ones((n, N, N), jnp.bfloat16) / N,
                       NamedSharding(mesh, P("x")))
    step(x).block_until_ready()
    tmp = str(ROOT / ".bench_trace" / "fixture")
    with Tracer(tmp):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                x = step(x)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(SLEEP_S)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copyfile(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out}: {os.path.getsize(out)} bytes, {n} devices")


if __name__ == "__main__":
    main(sys.argv[1])
