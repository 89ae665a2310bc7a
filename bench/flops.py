"""Model operations and bytes from a configuration's widths alone.

The counts are of the work the model needs, not of what an implementation
executes: recomputation and padding are not counted, so a share of a peak
built on them can only fall below the true one. Widths use the names of the
configuration files (HF ``config.json`` keys)."""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim", d // h), "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o projections and
    the gated (SwiGLU) feed-forward's gate, up and down."""
    x = dims(cfg)
    attn = x["d"] * x["h"] * x["dh"] * 2 + x["d"] * x["kv"] * x["dh"] * 2
    return attn + 3 * x["d"] * x["ff"]


def head_params(cfg: Dict[str, Any]) -> int:
    x = dims(cfg)
    return x["d"] * x["v"]


def causal_pairs(seq: int) -> int:
    """Query-key pairs causal attention scores in one sequence."""
    return seq * (seq + 1) // 2


def attn_flops_per_pair(cfg: Dict[str, Any]) -> int:
    """Forward operations of one query-key pair in all layers: the score
    (2·dh per head) and its weighted value (2·dh per head)."""
    x = dims(cfg)
    return 4 * x["h"] * x["dh"] * x["layers"]


def train_step_flops(cfg: Dict[str, Any], batch: int, seq: int) -> int:
    """Forward and backward operations of one training step: 6 per weight
    per token for the layers' matmuls and the LM head, and 3x the forward
    score and value products of causal attention."""
    x = dims(cfg)
    tokens = batch * seq
    weights = x["layers"] * layer_matmul_params(cfg) + head_params(cfg)
    return 6 * weights * tokens + 3 * attn_flops_per_pair(cfg) * batch \
        * causal_pairs(seq)


def serve_flops(cfg: Dict[str, Any],
                requests: Iterable[Tuple[int, int]]) -> int:
    """Forward operations to serve ``(prompt_len, output_len)`` requests:
    every fed token (the prompt and every output token but the last) through
    the layers' matmuls and attention over its context, and the LM head
    once per output token."""
    x = dims(cfg)
    total = 0
    for p, n in requests:
        fed = p + max(n - 1, 0)
        total += 2 * x["layers"] * layer_matmul_params(cfg) * fed
        total += attn_flops_per_pair(cfg) * causal_pairs(fed)
        total += 2 * head_params(cfg) * n
    return total


def train_matmul_bytes(cfg: Dict[str, Any]) -> int:
    """Least bytes a training step's matmuls move: every weight read in
    bf16 once by the forward product and once by each of the two backward
    products. Activations are left out, so the bound is a lower one."""
    x = dims(cfg)
    weights = x["layers"] * layer_matmul_params(cfg) + head_params(cfg)
    return 2 * 3 * weights
