"""Weights and training batches made by the benchmark from ``--seed``.

The program under test and the reference both receive what these
functions make; neither makes its own.  Layer ``l``'s matrices depend only
on ``fold_in(layer_key, l)``, so the program's stacked tree (``stacked``,
one jitted call on the device) and the reference's layer-at-a-time
rebuild (``layer``) hold the same numbers.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: Dict) -> Dict[str, int]:
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return dict(d=c["hidden_size"], ff=c["intermediate_size"],
                h=c["num_attention_heads"], g=c["num_key_value_heads"],
                hd=hd, L=c["num_hidden_layers"], V=c["vocab_size"])


def keys(seed32: int):
    """(embedding key, layer key, data key) of a run."""
    k = jax.random.PRNGKey(seed32)
    return jax.random.split(k, 3)


def layer(key, c: Dict, l) -> Dict:
    """Layer ``l``'s float32 weights in the program's tree layout
    (``x @ w`` orientation, norm scales near 1)."""
    m = dims(c)
    d, ff, qd, kvd = m["d"], m["ff"], m["h"] * m["hd"], m["g"] * m["hd"]
    ks = jax.random.split(jax.random.fold_in(key, l), 9)

    def mat(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])

    def norm(k):
        return 1.0 + 0.1 * jax.random.normal(k, (d,), jnp.float32)

    return {
        "pre_norm_scale": norm(ks[0]),
        "attn": {"wq": mat(ks[1], (d, qd)), "wk": mat(ks[2], (d, kvd)),
                 "wv": mat(ks[3], (d, kvd)), "wo": mat(ks[4], (qd, d))},
        "ffn_norm_scale": norm(ks[5]),
        "mlp": {"w_up": mat(ks[6], (d, ff)), "w_gate": mat(ks[7], (d, ff)),
                "w_down": mat(ks[8], (ff, d))},
    }


def embed(key, c: Dict):
    m = dims(c)
    return 0.02 * jax.random.normal(key, (m["V"], m["d"]), jnp.float32)


def final_norm(key, c: Dict):
    k = jax.random.fold_in(key, 1 << 20)
    return 1.0 + 0.1 * jax.random.normal(k, (dims(c)["d"],), jnp.float32)


def stacked(seed32: int, c: Dict) -> Dict:
    """The whole float32 tree, layers stacked on a leading axis (call
    inside ``jax.jit``)."""
    ke, kl, _ = keys(seed32)
    L = dims(c)["L"]
    stage = jax.vmap(lambda l: layer(kl, c, l))(jnp.arange(L))
    return {"embed": embed(ke, c), "stage": {"b0_attn": stage},
            "final_norm_scale": final_norm(ke, c)}


def train_batch(seed32: int, step, batch: int, seq: int, vocab: int):
    """Step ``step``'s rows: uniform token ids, labels shifted by one."""
    _, _, kd = keys(seed32)
    toks = jax.random.randint(jax.random.fold_in(kd, step), (batch, seq + 1),
                              0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
