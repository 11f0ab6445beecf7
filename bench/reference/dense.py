"""Plain float32 reference of a dense decoder: yi (Llama) and OLMo.

Straight ``jax.numpy`` over the whole sequence, with no kernel, cache,
paging or batching of requests, every product at
``Precision.HIGHEST``. It follows the published blocks:

    x = embed[tokens]
    per layer:  h = norm(x);  x += Attn(h)      (causal, RoPE, GQA)
                h = norm(x);  x += W_down(silu(W_gate h) * W_up h)
    logits = norm_f(x) @ unembed                (embed^T when tied)

norm is RMSNorm with a scale (yi) or LayerNorm without scale or bias
(OLMo). RoPE rotates the two halves of each head (the Llama/HF layout).
Weights are rebuilt one layer at a time from the seed (``bench.weights``)
and upcast to float32, so the reference holds nothing the program made.

``quant=True`` is the control: the same forward with every weight matrix
and every activation that enters one rounded to float8 (e4m3, per output
channel and per token scales), the next precision below the bfloat16 the
configurations serve in.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fq(x, axis):
    """Round ``x`` to float8 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, x, w, quant, w_in_axes):
    """einsum of an activation and a weight, optionally through float8."""
    if quant:
        x = _fq(x, -1)
        w = _fq(w, w_in_axes)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _norm(x, scale, kind, eps):
    if kind == "layernorm_np":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * scale


def _rope(x, theta):
    """x: (G, S, H, D), positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("kind", "eps", "theta", "quant"))
def _layer(x, w, *, kind, eps, theta, quant):
    """One decoder layer over (G, S, d) float32."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    G, S, _ = x.shape
    a = w["attn"]
    h = _norm(x, w["norm_in"].get("scale"), kind, eps)
    q = _rope(_mm("gsd,dhk->gshk", h, a["wq"], quant, 0), theta)
    k = _rope(_mm("gsd,dhk->gshk", h, a["wk"], quant, 0), theta)
    v = _mm("gsd,dhk->gshk", h, a["wv"], quant, 0)
    rep = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("gqhk,gshk->ghqs", q, k, precision=HIGHEST)
    s = s / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ghqs,gshk->gqhk", p, v, precision=HIGHEST)
    if quant:
        o = _fq(o.reshape(G, S, -1), -1).reshape(o.shape)
        wo = _fq(a["wo"], (0, 1))
    else:
        wo = a["wo"]
    x = x + jnp.einsum("gqhk,hkd->gqd", o, wo, precision=HIGHEST)
    m = w["mlp"]
    h = _norm(x, w["norm_mlp"].get("scale"), kind, eps)
    up = jax.nn.silu(_mm("gsd,df->gsf", h, m["wi_gate"], quant, 0)) \
        * _mm("gsd,df->gsf", h, m["wi_up"], quant, 0)
    return x + _mm("gsf,fd->gsd", up, m["wo"], quant, 0)


@partial(jax.jit, static_argnames=("kind", "eps", "quant"))
def _head(x, outer, targets, *, kind, eps, quant):
    """Logits of (G, S, d) and, per position, the largest logit, the
    target token's logit and the argmax (targets < 0 are ignored)."""
    outer = jax.tree.map(lambda a: a.astype(jnp.float32), outer)
    h = _norm(x, outer["norm_f"].get("scale"), kind, eps)
    emb = outer["embed"]
    w = emb["unembed"] if "unembed" in emb else emb["tok"].T
    logits = _mm("gsd,dv->gsv", h, w, quant, 0)
    best = jnp.max(logits, -1)
    tgt = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                              -1)[..., 0]
    return best, tgt, jnp.argmax(logits, -1).astype(jnp.int32)


def forward(dm: weights.Dims, cfg: Dict, seed: int, tokens: np.ndarray,
            targets: np.ndarray, quant: bool = False, group: int = 4):
    """Run the reference over ``tokens`` (N, S) int32.

    Returns numpy (best, target_logit, argmax), each (N, S): at position
    ``t`` the logits that predict token ``t + 1``. N must be a multiple
    of ``group``; sequences are processed ``group`` at a time, one layer
    at a time, so the peak holds one layer's weights and one group's
    attention scores.
    """
    kind, eps = cfg["norm_kind"], float(cfg["norm_eps"])
    theta = float(cfg["rope_theta"])
    N = tokens.shape[0]
    outer = weights.outer(dm, seed)
    emb = outer["embed"]["tok"]
    xs = [jnp.take(emb, jnp.asarray(tokens[i:i + group]), axis=0
                   ).astype(jnp.float32) for i in range(0, N, group)]
    for l in range(dm.layers):
        w = weights.layer(dm, seed, l)
        xs = [_layer(x, w, kind=kind, eps=eps, theta=theta, quant=quant)
              for x in xs]
        del w
    out = [_head(x, outer, jnp.asarray(targets[i * group:(i + 1) * group]),
                 kind=kind, eps=eps, quant=quant)
           for i, x in enumerate(xs)]
    return tuple(np.concatenate([np.asarray(o[j]) for o in out])
                 for j in range(3))
