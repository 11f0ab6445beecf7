"""Seeded draws that every family's weight builder (``bench/families``)
makes its leaves from, on the device and in the served dtype.

Every leaf is drawn from its own key, ``fold_in(root, leaf id)``, and a
layer-stacked leaf from ``fold_in(leaf key, layer)``, where the root key
is the run's seed. A family fixes its leaf ids forever, so that a seed
names the same weights in every PR, and builds a layer from the root key
and the layer's index alone: the reference then rebuilds layer ``l`` bit
for bit without the rest, and never takes an array the program holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .traffic import seed_words

DTYPE = jnp.bfloat16


def words(seed: int):
    """The seed as the uint32 words the root key is made of: pass these,
    not the seed, into a jitted builder, so every seed shares one
    program."""
    return jnp.asarray(seed_words(seed))


def root(words):
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def leaf_key(root, leaf_id: int, layer=None):
    """A leaf's key; with ``layer`` (may be traced), its layer's slice."""
    key = jax.random.fold_in(root, leaf_id)
    return key if layer is None else jax.random.fold_in(key, layer)


def normal(key, shape, std):
    """A matrix: normal(0, std) drawn in float32, stored in DTYPE."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(DTYPE)


def scale(key, n):
    """A norm scale: 1 + normal(0, 0.1), so a norm that drops it shows."""
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(DTYPE)
