"""The one traffic generator: a request schedule from a mix's parameters.

A mix file (``bench/traffic/<name>.json``) gives

    rate_per_s        offered load, requests per second (open loop)
    prompt_tokens     {"values": [...], "weights": [...]}: the support
    output_tokens     {"values": [...], "weights": [...]}
    max_total_tokens  prompt + output never exceeds it, for every pair of
                      the supports (checked by the tests)
    pattern_seed      draws the mix's one arrival pattern

Every seed gets the same work, arrivals included, in another order. The
window's ``n = round(rate * seconds)`` requests take their lengths from
one fixed multiset (the weights, rounded by largest remainder) and their
gaps from the exponential's quantiles, scaled to fill the window; the
mix's ``pattern_seed`` orders them, once for every seed: the gaps
shuffled, the lengths spread so that every stretch of the pattern holds
each length about as often as the whole does. The run's seed picks
where in that cyclic pattern the window starts, and draws the prompt
tokens. So every seed meets the same bursts with the same requests in
them, at other times in the window: queueing, which a shuffle of the
gaps changed from seed to seed, does not change with the seed, nor does
the mix a window serves when it serves only part of the pattern, and
Poisson's inter-arrival law is kept.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclass
class Request:
    idx: int
    due: float            # seconds after the window opens
    prompt: List[int]
    max_new: int


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (JAX keys take 32 bits
    a word; a bare ``jax.random.key`` would drop the high bits)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _quota(values: Sequence[int], weights: Sequence[float],
           n: int) -> List[int]:
    """``n`` draws in exact proportion to ``weights`` (largest remainder)."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    exact = w * n
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return [int(v) for v, c in zip(values, counts) for _ in range(c)]


def _strata(lengths: List[int], rng: np.random.Generator) -> List[int]:
    """``lengths`` in an order where every stretch keeps the mix: the
    ``c`` copies of a value fall one into each of ``c`` equal strata of
    the sequence, at a random place in each. A window that serves only
    part of the pattern, as one above the knee does, then serves the same
    mix wherever it enters the pattern."""
    n = len(lengths)
    keyed = [((j + rng.random()) * n / c, v)
             for v, c in Counter(lengths).items() for j in range(c)]
    return [v for _, v in sorted(keyed)]


def scaled(values: Sequence[int], divisor: int) -> List[int]:
    return [max(2, int(v) // divisor) for v in values]


def support(mix: Dict, prompt_div: int = 1,
            output_div: int = 1) -> Tuple[List[int], List[int]]:
    """The prompt and output lengths the mix can produce."""
    return (scaled(mix["prompt_tokens"]["values"], prompt_div),
            scaled(mix["output_tokens"]["values"], output_div))


def pairs(mix: Dict, prompt_div: int = 1,
          output_div: int = 1) -> List[Tuple[int, int]]:
    """Every (prompt, output) length pair the mix can produce."""
    prompts, outs = support(mix, prompt_div, output_div)
    return [(p, o) for p in prompts for o in outs]


def schedule(mix: Dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None, prompt_div: int = 1,
             output_div: int = 1) -> List[Request]:
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    # the pattern: one order of (gap, prompt, output) for every seed
    pattern = np.random.default_rng(seed_words(mix["pattern_seed"]))
    prompts, outs = support(mix, prompt_div, output_div)
    plens = _strata(_quota(prompts, mix["prompt_tokens"]["weights"], n),
                    pattern)
    olens = _strata(_quota(outs, mix["output_tokens"]["weights"], n),
                    pattern)
    # gaps: quantiles of Exp(rate), scaled so the n arrivals fill the
    # window; the first request is due when the window opens
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = gaps / gaps.sum() * seconds
    pattern.shuffle(gaps)
    # the seed: where the window starts in the cyclic pattern, and tokens
    rng = np.random.default_rng(seed_words(seed))
    order = (np.arange(n) + rng.integers(n)) % n
    gaps = gaps[order]
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reqs = []
    for i, j in enumerate(order):
        toks = rng.integers(1, vocab, size=plens[j]).tolist()
        reqs.append(Request(i, float(dues[i]), toks, int(olens[j])))
    return reqs
