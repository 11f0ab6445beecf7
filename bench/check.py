"""Whether what the window served is correct.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
longest, is run through the plain float32 reference (one forward over
prompt + served tokens each). For every served token the reference's
gap is read: how far its logit lies below the reference's best at that
position. The largest gap over the sample is compared with the cell's
limit (``bench/limits/<cell>.json``), beside exact counts: tokens lost
in delivery, requests failed, sandbox violations.

The serving path is greedy (argmax), so a served token must be, to
bfloat16 rounding, the reference's best: a wrong page, mask, position,
KV write or delivered token moves the gap by the logits' own scale.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import model, spec, traffic

SAMPLE = 8          # sequences the reference runs, the longest among them
GROUP = 4           # sequences per reference call


def sample(records, seed: int, n: int = SAMPLE) -> List:
    """``n`` finished requests: the longest, then a seeded draw; fewer
    finished requests repeat (the reference runs a fixed shape)."""
    done = [r for r in records if r.done and r.error is None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                       -r.req.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(
        np.append(traffic.seed_words(seed), np.uint32(0x5EED)))
    picked = [longest] + [rest[i] for i in rng.permutation(len(rest))
                          [: n - 1]]
    return [picked[i % len(picked)] for i in range(n)]


def arrays(picked, length: int):
    """(tokens, targets), each (N, length): prompt + served tokens, and at
    position t the served token that position t's logits chose (or -1)."""
    N = len(picked)
    toks = np.zeros((N, length), np.int32)
    tgts = np.full((N, length), -1, np.int32)
    for i, r in enumerate(picked):
        seq = list(r.req.prompt) + list(r.tokens)
        P = len(r.req.prompt)
        toks[i, : len(seq) - 1] = seq[:-1]
        tgts[i, P - 1: len(seq) - 1] = r.tokens
    return toks, tgts


def gaps(m: Dict, seed: int, picked, length: int) -> np.ndarray:
    """Per served token, the reference's best logit minus its logit."""
    ref = spec.reference(m["reference"])
    toks, tgts = arrays(picked, length)
    best, tgt, _ = ref.forward(model.family(m).Dims.of(m), m, seed, toks,
                               tgts, group=GROUP)
    return (best - tgt)[tgts >= 0]


def control_gaps(m: Dict, seed: int, picked, length: int) -> np.ndarray:
    """The control: at each served position, the token the float8
    reference puts first, and the float32 reference's gap for it."""
    ref = spec.reference(m["reference"])
    dm = model.family(m).Dims.of(m)
    toks, tgts = arrays(picked, length)
    _, _, low = ref.forward(dm, m, seed, toks, tgts, quant=True,
                            group=GROUP)
    low = np.where(tgts >= 0, low, -1)
    best, at_low, _ = ref.forward(dm, m, seed, toks, low, group=GROUP)
    return (best - at_low)[tgts >= 0]


def compared(values: Dict[str, float], limits: Dict) -> Dict[str, Dict]:
    """Each compared number beside its limit; ``ok`` when within."""
    out = {}
    for name, v in values.items():
        lim = limits[name]["limit"]
        out[name] = {"value": v, "limit": lim, "ok": bool(v <= lim)}
    return out
