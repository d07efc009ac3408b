"""Seeded weights, made on the device in one jitted call, from the leaf
tables of the configuration's architecture module.

The module gives the global leaves, ``{name: (shape, std)}``, and one or
more groups of layers, ``{group: (n_layers, {name: (shape, std)})}``;
``std`` is None for a norm scale.  Every leaf comes from its own key: the
i-th global leaf from ``fold_in(seed key, i)``, and the j-th leaf of the
layer tables, counted through the groups in order, from
``fold_in(fold_in(seed key, 16 + j), layer)`` with the layer's place in
its group, so the plain reference can remake any single layer on its own.
Leaves are drawn with standard deviation ``std``, norm scales around 1,
then cast to the type they are served in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FIRST_LAYER_LEAF = 16  # the leaf number of the first layer leaf; global leaves come before it


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (a seed may need more than 32
    bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def tables(arch, m: dict) -> tuple:
    """The architecture's leaf tables for configuration ``m``, as hashable
    tuples: ``(globals, groups)``."""
    glob = tuple((name, tuple(shape), std) for name, (shape, std) in arch.global_leaves(m).items())
    if len(glob) > FIRST_LAYER_LEAF:
        raise ValueError(f"{len(glob)} global leaves; leaf numbers from {FIRST_LAYER_LEAF} are the layers'")
    groups = tuple(
        (group, int(n), tuple((name, tuple(shape), std) for name, (shape, std) in leaves.items()))
        for group, (n, leaves) in arch.layer_groups(m).items()
    )
    return glob, groups


def _draw(key, shape, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if std is None else std * x
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(tabs: tuple, key, dtype_name: str) -> dict:
    """All weights of ``tables(...)``: each global leaf by its name, and
    each group's leaves stacked on a leading layer axis under the group's
    name."""
    glob, groups = tabs
    dtype = jnp.dtype(dtype_name)
    out, j = {}, FIRST_LAYER_LEAF
    for group, n, leaves in groups:
        def layer(i, leaves=leaves, j=j):
            return {name: _draw(jax.random.fold_in(jax.random.fold_in(key, j + k), i), shape, std, dtype)
                    for k, (name, shape, std) in enumerate(leaves)}

        out[group] = jax.vmap(layer)(jnp.arange(n))
        j += len(leaves)
    for i, (name, shape, std) in enumerate(glob):
        out[name] = _draw(jax.random.fold_in(key, i), shape, std, dtype)
    return out


def make(arch, m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """All weights of configuration ``m`` from ``seed``."""
    return _make(tables(arch, m), seed_key(seed), dtype)
