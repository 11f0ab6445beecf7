"""The dense decoder family: yi (Llama) and OLMo.

A configuration file names its family with ``"family"`` (absent means
this one); the harness loads ``bench/families/<family>.py`` by that name
and asks it for:

    KEYS                     the published keys ``bench.model.flat`` keeps
    program_config(m)        the program's ``ModelConfig``
    Dims.of(m)               the sizes the weight builder needs
    make(dm, seed)           the whole served tree, one jitted call
    layer(dm, seed, l)       layer ``l`` alone, bit for bit as ``make``
    outer(dm, seed)          embedding, unembedding, final norm
    decode_work(run, steps)  (FLOPs, bytes) the window's decode steps need
    prefill_flops(m, prompt) FLOPs a prompt's prefill needs

The served tree is laid out as the program's ``paged_decode_step`` reads
it: ``embed`` {tok (V, d), unembed (d, V) unless tied}, ``stack.pos0``
with every leaf stacked over the layers (wq (d, H, Dh), wk/wv (d, Hkv,
Dh), wo (H, Dh, d), mlp wi_gate/wi_up (d, F), wo (F, d), norm scales
(d,)), and ``norm_f``. Matrices are normal(0, 1/sqrt(fan_in)), norm
scales 1 + normal(0, 0.1) (``bench.weights``).

Work counts are what the algorithm needs (``bench.work``): a decode step
reads every weight once and each token's live K/V, whatever the program
copies or walks besides.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import weights, work

KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "vocab_size",
        "rope_theta", "tie_word_embeddings", "max_position_embeddings")


def program_config(m: Dict):
    """The program's ``ModelConfig`` for these sizes."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=m["name"], family="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_kind=m["rope_kind"], rope_theta=float(m["rope_theta"]),
        norm_kind=m["norm_kind"], norm_eps=m["norm_eps"],
        mlp_kind=m["mlp_kind"], tie_embeddings=m["tie_word_embeddings"],
        max_seq_len=m["max_pages_per_seq"] * m["page_tokens"])


# -- weights ----------------------------------------------------------------
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
    key = lambda name: weights.leaf_key(root, _IDS[name], l)
    w = {n: weights.normal(key(n), shape, fan ** -0.5)
         for n, (shape, fan) in _layer_shapes(dm).items()}
    out = {
        "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                 "wo": w["wo"]},
        "mlp": {"wi_gate": w["wi_gate"], "wi_up": w["wi_up"],
                "wo": w["mlp_wo"]},
        "norm_in": {}, "norm_mlp": {},
    }
    if dm.norm_scales:
        out["norm_in"] = {"scale": weights.scale(key("norm_in"), dm.d)}
        out["norm_mlp"] = {"scale": weights.scale(key("norm_mlp"), dm.d)}
    return out


def _outer(dm: Dims, root) -> Dict:
    key = lambda name: weights.leaf_key(root, _IDS[name])
    embed = {"tok": weights.normal(key("tok"), (dm.vocab, dm.d),
                                   dm.d ** -0.5)}
    if not dm.tied:
        embed["unembed"] = weights.normal(key("unembed"), (dm.d, dm.vocab),
                                          dm.d ** -0.5)
    norm_f = {"scale": weights.scale(key("norm_f"), dm.d)} \
        if dm.norm_scales else {}
    return {"embed": embed, "norm_f": norm_f}


@partial(jax.jit, static_argnums=0)
def _make_all(dm: Dims, words):
    root = weights.root(words)
    stack = jax.vmap(lambda l: _one_layer(dm, root, l))(
        jnp.arange(dm.layers))
    out = _outer(dm, root)
    out["stack"] = {"pos0": stack}
    return out


@partial(jax.jit, static_argnums=0)
def _make_layer(dm: Dims, words, l):
    return _one_layer(dm, weights.root(words), l)


@partial(jax.jit, static_argnums=0)
def _make_outer(dm: Dims, words):
    return _outer(dm, weights.root(words))


def make(dm: Dims, seed: int) -> Dict:
    """The whole served tree, in bf16, from one jitted call."""
    return _make_all(dm, weights.words(seed))


def layer(dm: Dims, seed: int, l: int) -> Dict:
    """Layer ``l`` alone, bit for bit as ``make`` stacks it."""
    return _make_layer(dm, weights.words(seed), jnp.int32(l))


def outer(dm: Dims, seed: int) -> Dict:
    """Embedding, unembedding and final norm, as ``make`` draws them."""
    return _make_outer(dm, weights.words(seed))


# -- work ---------------------------------------------------------------------
def layer_matmul_params(m: Dict) -> int:
    d, H, Hkv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    D, F = m["head_dim"], m["intermediate_size"]
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * F


def weight_bytes(m: Dict) -> int:
    """Bytes a decode step must read: every layer's matrices and norm
    scales, the unembedding (the embedding itself when tied), the final
    norm. The embedding rows a batch looks up are negligible."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    norms = 0 if m["norm_kind"] == "layernorm_np" else (2 * L + 1) * d
    return work.BYTES * (L * layer_matmul_params(m) + work.unembed_params(m)
                         + norms)


def decode_token_flops(m: Dict, live: int) -> int:
    """One sequence's token in a decode step whose KV holds ``live``
    tokens (this one included)."""
    mm = m["num_hidden_layers"] * layer_matmul_params(m) \
        + work.unembed_params(m)
    return 2 * mm + work.attn_flops(m, live)


def decode_window(m: Dict, steps: int, lives) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode steps of a window need: ``steps``
    executions, one token per entry of ``lives`` (its live length).
    Weights are read once per step; each token reads its live K/V and
    writes its own."""
    flops = sum(decode_token_flops(m, n) for n in lives)
    kv = work.kv_token_bytes(m)
    nbytes = steps * weight_bytes(m) + sum(n * kv + kv for n in lives)
    return float(flops), float(nbytes)


def decode_work(run, steps: int) -> Optional[Tuple[float, float]]:
    """``decode_window`` over the run's decode tokens (``Run.decode_lives``);
    None where no decode token arrived in the window."""
    lives = run.decode_lives()
    return decode_window(run.m, steps, lives) if lives else None


def prefill_flops(m: Dict, prompt: int) -> int:
    """A prompt's prefill: every matmul over every position, causal
    attention (position t attends to t + 1 keys), and the unembedding of
    the last position only."""
    causal = prompt * (prompt + 1) // 2
    return 2 * prompt * m["num_hidden_layers"] * layer_matmul_params(m) \
        + work.attn_flops(m, causal) + 2 * work.unembed_params(m)
