"""The reference's reading of served tokens: one full forward pass over each
sampled request's prompt and served tokens, in float32 at the highest
matmul precision, on weights made again from the seed.

At each position that produced a served token it reads how far that
token's logit lies below the best logit there. With ``control`` it also
reads the same gap for the token that the float8 forward pass ranks first
at that position, which is what a lower-precision program would serve."""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import model as ref

ROW_BLOCK = 512


def gaps(cfg: Dict[str, Any], seed: int, layout,
         served: Sequence[Tuple[np.ndarray, List[int]]], s_pad: int,
         rows: int, control: bool = False) -> Dict[str, Any]:
    """``served``: (prompt, output tokens) of each sampled request, each
    fitting ``s_pad`` positions; ``rows``: the most output tokens of all of
    them together (the padded size, fixed so that one program serves every
    sample)."""
    dm = ref.dims_of(cfg)
    k = len(served)
    toks = np.zeros((k, s_pad), np.int32)
    b_idx = np.zeros((rows,), np.int32)
    s_idx = np.zeros((rows,), np.int32)
    target = np.zeros((rows,), np.int32)
    valid = np.zeros((rows,), bool)
    r = 0
    for i, (prompt, out) in enumerate(served):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        n = len(out)
        b_idx[r:r + n] = i
        s_idx[r:r + n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        target[r:r + n] = out
        valid[r:r + n] = True
        r += n
    if r > rows:
        raise ValueError(f"{r} served tokens exceed the {rows} rows")

    @jax.jit
    def read(params, toks, b_idx, s_idx, target):
        h = ref.hidden(params, toks, dm)[b_idx, s_idx]
        hc = ref.hidden(params, toks, dm, quant=True)[b_idx, s_idx] \
            if control else h

        def one(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * ROW_BLOCK,
                                                        ROW_BLOCK)
            z = ref.logits(params, sl(h))
            best = jnp.max(z, -1)
            served_gap = best - jnp.take_along_axis(
                z, sl(target)[:, None], -1)[:, 0]
            if not control:
                return served_gap, served_gap
            pick = jnp.argmax(ref.logits(params, sl(hc), quant=True), -1)
            return served_gap, best - jnp.take_along_axis(
                z, pick[:, None], -1)[:, 0]

        a, b = jax.lax.map(one, jnp.arange(rows // ROW_BLOCK))
        return a.reshape(-1), b.reshape(-1)

    params = jax.jit(lambda k: weights.make_params(layout, k))(
        weights.seed_key(seed))
    served_gap, control_gap = read(params, toks, b_idx, s_idx, target)
    served_gap = np.asarray(served_gap)[valid]
    out = {"gap": float(np.max(served_gap)), "tokens": int(valid.sum())}
    if control:
        out["control_gap"] = float(np.max(np.asarray(control_gap)[valid]))
    return out
