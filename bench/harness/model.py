"""The program's model configuration object, built from a configuration
file's published keys (the file is the configuration as it is run)."""

from __future__ import annotations

from typing import Dict

from bench.harness.weights import dims


def lm_config(c: Dict, act_fmt=None):
    from repro.models.lm import LMConfig
    m = dims(c)
    return LMConfig(name=c.get("model_type", "model"), n_layers=m["L"],
                    d_model=m["d"], n_heads=m["h"], n_kv_heads=m["g"],
                    head_dim=m["hd"], d_ff=m["ff"], vocab=m["V"],
                    pattern=("attn",), mlp_kind="swiglu",
                    rope_theta=c["rope_theta"],
                    tie_embeddings=c["tie_word_embeddings"],
                    max_seq=max(c["max_position_embeddings"], 8192),
                    dtype=c.get("compute_dtype", "bfloat16"),
                    act_fmt=act_fmt)
