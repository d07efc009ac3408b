"""Seeded weights of a dense decoder, made on the device in one jitted call.

Every leaf comes from its own key, ``fold_in(seed key, leaf number)``, and
a stacked per-layer leaf from ``fold_in(leaf key, layer)``, so the plain
reference can remake any single layer on its own.  Projections are drawn
with standard deviation 1/sqrt(fan-in), the embedding with 0.02, and norm
scales around 1, then cast to the type they are served in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ffn_norm", "wg", "wu", "wd")


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (a seed may need more than 32
    bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def dims(m: dict) -> dict:
    return dict(
        d=m["hidden_size"], h=m["num_attention_heads"], kv=m["num_key_value_heads"],
        hd=m["head_dim"], f=m["intermediate_size"], v=m["vocab_size"],
        n=m["num_hidden_layers"],
    )


def layer_shapes(m: dict) -> dict:
    """Shape and init standard deviation (None: a norm scale) of each
    per-layer leaf."""
    z = dims(m)
    d, h, kv, hd, f = z["d"], z["h"], z["kv"], z["hd"], z["f"]
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, h, hd), d ** -0.5),
        "wk": ((d, kv, hd), d ** -0.5),
        "wv": ((d, kv, hd), d ** -0.5),
        "wo": ((h, hd, d), (h * hd) ** -0.5),
        "q_norm": ((hd,), None),
        "k_norm": ((hd,), None),
        "ffn_norm": ((d,), None),
        "wg": ((d, f), d ** -0.5),
        "wu": ((d, f), d ** -0.5),
        "wd": ((f, d), f ** -0.5),
    }


def _draw(key, shape, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if std is None else std * x
    return x.astype(dtype)


def layer(m: dict, key, i, dtype):
    """Leaves of layer ``i`` (a traced or Python int)."""
    out = {}
    for j, (name, (shape, std)) in enumerate(layer_shapes(m).items()):
        out[name] = _draw(jax.random.fold_in(jax.random.fold_in(key, 16 + j), i), shape, std, dtype)
    return out


def globals_(m: dict, key, dtype):
    z = dims(m)
    out = {
        "embed": _draw(jax.random.fold_in(key, 0), (z["v"], z["d"]), 0.02, dtype),
        "final_norm": _draw(jax.random.fold_in(key, 1), (z["d"],), None, dtype),
    }
    if not m["tie_word_embeddings"]:
        out["head"] = _draw(jax.random.fold_in(key, 2), (z["d"], z["v"]), z["d"] ** -0.5, dtype)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(m_items, key, dtype_name):
    m = dict(m_items)
    dtype = jnp.dtype(dtype_name)
    layers = jax.vmap(lambda i: layer(m, key, i, dtype))(jnp.arange(m["num_hidden_layers"]))
    return {**globals_(m, key, dtype), "layers": layers}


def make(m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """All weights: ``embed``, ``final_norm``, ``head`` when untied, and
    ``layers`` with every per-layer leaf stacked on a leading layer axis."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "vocab_size", "num_hidden_layers", "tie_word_embeddings")
    return _make(tuple((k, m[k]) for k in keys), seed_key(seed), dtype)
