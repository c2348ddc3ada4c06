"""A configuration file (``bench/configs/<name>.json``) and the program's
``ArchConfig`` built from its published widths."""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

NORMS = {"nonparametric_layernorm": "nonparam_ln", "rmsnorm": "rmsnorm"}


def load(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def arch_config(cfg: dict):
    """The program's model configuration for ``cfg``: served in bfloat16
    through the Pallas kernels."""
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU (silu) MLPs are served")
    return ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp_type="swiglu", norm_type=NORMS[cfg["norm"]],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=jnp.dtype(cfg["torch_dtype"]), remat=False,
        kernel_impl="pallas", source=cfg["source"],
    )
