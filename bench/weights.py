"""Seeded weights for a dense decoder, made on the device in one jitted call.

Every leaf is drawn from its own key, ``fold_in(key(seed), leaf id)``,
and a layer-stacked leaf from ``fold_in(leaf key, layer)``, vmapped over
the layers. So ``layer(seed, l)`` rebuilds layer ``l`` bit for bit
without the rest: the reference regenerates the weights one layer at a
time from the seed, and never takes an array the program holds.

The tree is laid out as the program's ``paged_decode_step`` reads it:
``embed`` {tok (V, d), unembed (d, V) unless tied}, ``stack.pos0`` with
every leaf stacked over the layers (wq (d, H, Dh), wk/wv (d, Hkv, Dh),
wo (H, Dh, d), mlp wi_gate/wi_up (d, F), wo (F, d), norm scales (d,)),
and ``norm_f``. Matrices are normal(0, 1/sqrt(fan_in)); norm scales
1 + normal(0, 0.1), so a norm that drops its scale shows.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from .traffic import seed_words


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm_scales: bool   # False for OLMo's non-parametric LayerNorm

    @classmethod
    def of(cls, c: Dict) -> "Dims":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], tied=c["tie_word_embeddings"],
                   norm_scales=c["norm_kind"] != "layernorm_np")


# leaf ids: fixed forever, so a seed names the same weights in every PR
_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "wi_gate", "wi_up", "mlp_wo",
                 "norm_in", "norm_mlp")
_IDS = {name: i for i, name in enumerate(
    ("tok", "unembed", "norm_f") + _LAYER_LEAVES)}

DTYPE = jnp.bfloat16


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(DTYPE)


def _scale(key, n):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(DTYPE)


def _layer_shapes(dm: Dims):
    d, H, Hkv, Dh, F = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff
    return {
        "wq": ((d, H, Dh), d), "wk": ((d, Hkv, Dh), d),
        "wv": ((d, Hkv, Dh), d), "wo": ((H, Dh, d), H * Dh),
        "wi_gate": ((d, F), d), "wi_up": ((d, F), d),
        "mlp_wo": ((F, d), F),
    }


def _one_layer(dm: Dims, root, l) -> Dict:
    """Layer ``l``'s leaves from the root key (``l`` may be traced)."""
    key = lambda name: jax.random.fold_in(
        jax.random.fold_in(root, _IDS[name]), l)
    w = {n: _normal(key(n), shape, fan ** -0.5)
         for n, (shape, fan) in _layer_shapes(dm).items()}
    out = {
        "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                 "wo": w["wo"]},
        "mlp": {"wi_gate": w["wi_gate"], "wi_up": w["wi_up"],
                "wo": w["mlp_wo"]},
        "norm_in": {}, "norm_mlp": {},
    }
    if dm.norm_scales:
        out["norm_in"] = {"scale": _scale(key("norm_in"), dm.d)}
        out["norm_mlp"] = {"scale": _scale(key("norm_mlp"), dm.d)}
    return out


def _root(words):
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _outer(dm: Dims, root) -> Dict:
    key = lambda name: jax.random.fold_in(root, _IDS[name])
    embed = {"tok": _normal(key("tok"), (dm.vocab, dm.d), dm.d ** -0.5)}
    if not dm.tied:
        embed["unembed"] = _normal(key("unembed"), (dm.d, dm.vocab),
                                   dm.d ** -0.5)
    norm_f = {"scale": _scale(key("norm_f"), dm.d)} if dm.norm_scales \
        else {}
    return {"embed": embed, "norm_f": norm_f}


@partial(jax.jit, static_argnums=0)
def _make_all(dm: Dims, words):
    root = _root(words)
    stack = jax.vmap(lambda l: _one_layer(dm, root, l))(
        jnp.arange(dm.layers))
    out = _outer(dm, root)
    out["stack"] = {"pos0": stack}
    return out


@partial(jax.jit, static_argnums=0)
def _make_layer(dm: Dims, words, l):
    return _one_layer(dm, _root(words), l)


@partial(jax.jit, static_argnums=0)
def _make_outer(dm: Dims, words):
    return _outer(dm, _root(words))


def make(dm: Dims, seed: int) -> Dict:
    """The whole served tree, in bf16, from one jitted call."""
    return _make_all(dm, jnp.asarray(seed_words(seed)))


def layer(dm: Dims, seed: int, l: int) -> Dict:
    """Layer ``l`` alone, bit for bit as ``make`` stacks it."""
    return _make_layer(dm, jnp.asarray(seed_words(seed)), jnp.int32(l))


def outer(dm: Dims, seed: int) -> Dict:
    """Embedding, unembedding and final norm, as ``make`` draws them."""
    return _make_outer(dm, jnp.asarray(seed_words(seed)))
