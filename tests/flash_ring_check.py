"""One dense two-block ``sp_period`` forward and gradient on a four-device
TP ring (``data=1 x model=4``, cais), with the attention core forced onto
the Pallas flash kernel (run in the Pallas interpreter) and again on the XLA
fallback; prints one JSON line with the largest gaps and which core each
compiled step ran. ``test_attention_kernel.py`` starts it in a process of
its own with four virtual CPU devices."""
from __future__ import annotations

import functools
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.models.transformer as tr  # noqa: E402
from repro import sharding  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import tp  # noqa: E402
from repro.core.primitives import CAISConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402


def main() -> None:
    assert len(jax.devices()) == 4, jax.devices()
    cfg = get_arch("deepseek-7b").smoke().scaled(
        num_layers=2, d_model=128, num_heads=8, num_kv_heads=8,
        head_dim=128, d_ff=256)
    mesh = sharding.make_mesh((1, 4), ("data", "model"))
    tpc = tp.TPContext(mesh=mesh, backend="cais",
                       cais=CAISConfig(num_chunks=1))
    kinds = ("attn", "attn")
    ps = [tr.init_block(jax.random.key(3 + i), k, cfg, jnp.float32)
          for i, k in enumerate(kinds)]
    x = jax.random.normal(jax.random.key(5), (2, 256, 128), jnp.float32)

    def loss(x_, ps_):
        y, _ = tp.sp_period(tpc, x_, ps_, cfg, kinds, norm_kind=cfg.norm)
        return jnp.sum(y * y), y

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    def run():
        with sharding.use_mesh(mesh):
            text = step.lower(x, ps).compile().as_text()
            (_, y), grads = step(x, ps)
        return text, [np.asarray(a) for a in jax.tree.leaves((y, grads))]

    xla_text, want = run()
    tp._on_tpu = lambda: True
    ops.causal_flash_attention = functools.partial(
        ops.causal_flash_attention, interpret=True)
    jax.clear_caches()  # the path is chosen at trace time
    flash_text, got = run()
    gaps = [float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
            for a, b in zip(got, want)]
    print(json.dumps({
        "max_rel_gap": max(gaps), "leaves": len(gaps),
        "flash_scopes": [t.count("attn.flash") > 0
                         for t in (xla_text, flash_text)],
        "xla_scopes": [t.count("attn.xla") > 0
                       for t in (xla_text, flash_text)]}))


if __name__ == "__main__":
    main()
