"""The program's decoder LM (``repro.models.transformer``) built from a
configuration file's published keys."""
from __future__ import annotations

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def task(c: dict):
    from repro.fl.task import lm_task
    from repro.models.transformer import TransformerConfig

    heads = c["num_attention_heads"]
    dt = DTYPES[c["precision"]]
    return lm_task(TransformerConfig(
        name=c["name"], arch_type="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=heads, n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=c["qkv_bias"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        dtype=dt, param_dtype=dt, remat=c["assumed"]["remat"]))
