"""Builds the system under test from a configuration file: the registered
architecture with the file's widths, and the runtime the program gives it.
What the file states of precision is checked against that runtime, so a
run never measures another precision than the configuration states."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

WIDTHS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta"}


def arch_of(cfg: Dict[str, Any]):
    from repro.configs import get_arch

    arch = get_arch(cfg["program"]["arch"])
    kw = {field: cfg[key] for key, field in WIDTHS.items()}
    kw["head_dim"] = cfg.get("head_dim", 0)
    kw["tie_embeddings"] = cfg["tie_word_embeddings"]
    return dataclasses.replace(arch, **kw)


def runtime_of(cfg: Dict[str, Any], arch):
    from repro.launch import specs

    prog = cfg["program"]
    rt = specs.runtime_for(arch, tp_mode=prog["tp_mode"])
    if (rt.param_dtype, rt.compute_dtype) != (prog["param_dtype"],
                                              prog["compute_dtype"]):
        raise ValueError(
            f"the program runs params {rt.param_dtype} / compute "
            f"{rt.compute_dtype}; the configuration states "
            f"{prog['param_dtype']} / {prog['compute_dtype']}")
    return rt


def mesh_of(cfg: Dict[str, Any]):
    from repro.launch.mesh import MESHES

    return MESHES[cfg["program"]["mesh"]]()
