"""The traffic generator: deterministic in the seed, inside its supports,
the same work and the same arrivals for every seed."""

from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = spec.traffic(name)
    a = traffic.schedule(mix, 2**31 + 17, 20.0, 1000)
    b = traffic.schedule(mix, 2**31 + 17, 20.0, 1000)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_in_another_order(name):
    mix = spec.traffic(name)
    a = traffic.schedule(mix, 1, 45.0, 1000)
    b = traffic.schedule(mix, 2, 45.0, 1000)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 45.0)
    key = lambda rs: Counter((len(r.prompt), r.max_new) for r in rs)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_new for r in a) == Counter(r.max_new for r in b)
    assert [r.due for r in a] != [r.due for r in b] or key(a) != key(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_meet_one_cyclic_pattern(name):
    """Every seed serves the same (gap, prompt, output) cycle, entered at
    its own place: the bursts and the requests in them do not change."""
    mix = spec.traffic(name)

    def cycle(reqs):
        gaps = np.diff([r.due for r in reqs])
        return [(round(float(g), 9), len(r.prompt), r.max_new)
                for g, r in zip(gaps, reqs)]

    a = cycle(traffic.schedule(mix, 2**33 + 5, 51.0, 1000))
    b = cycle(traffic.schedule(mix, 7, 51.0, 1000))
    assert a != b
    # a holds the cycle but for its last gap, which no run reads (None)
    ring = a + [None]
    n = len(ring)

    def entered_at(k):
        return all(ring[(k + i) % n] in (None, x) for i, x in enumerate(b))

    assert any(entered_at(k) for k in range(n))


@pytest.mark.parametrize("name", MIXES)
def test_every_stretch_keeps_the_mix(name):
    """A window above the knee serves only a stretch of the pattern; each
    stretch holds every length within two of its share."""
    mix = spec.traffic(name)
    reqs = traffic.schedule(mix, 3, 51.0, 1000)
    n = len(reqs)
    for field, get in (("prompt_tokens", lambda r: len(r.prompt)),
                       ("output_tokens", lambda r: r.max_new)):
        w = np.asarray(mix[field]["weights"], np.float64)
        share = dict(zip(mix[field]["values"], w / w.sum()))
        for k in (n // 4, n // 2):
            for lo in range(0, n - k + 1, 7):
                c = Counter(get(r) for r in reqs[lo:lo + k])
                for v, s in share.items():
                    assert abs(c[v] - s * k) <= 2, (field, k, lo, v)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_the_support_and_the_cap(name):
    mix = spec.traffic(name)
    prompts = set(mix["prompt_tokens"]["values"])
    outs = set(mix["output_tokens"]["values"])
    reqs = traffic.schedule(mix, 99, 45.0, 500)
    for r in reqs:
        assert len(r.prompt) in prompts and r.max_new in outs
        assert len(r.prompt) + r.max_new <= mix["max_total_tokens"]
        assert all(1 <= t < 500 for t in r.prompt)
    dues = [r.due for r in reqs]
    assert dues[0] == 0.0 and all(0 <= d < 45.0 for d in dues)
    assert dues == sorted(dues)


def test_weights_set_the_proportions_exactly():
    mix = {"rate_per_s": 10.0, "max_total_tokens": 100, "pattern_seed": 1,
           "prompt_tokens": {"values": [4, 8], "weights": [3, 1]},
           "output_tokens": {"values": [2], "weights": [1]}}
    reqs = traffic.schedule(mix, 5, 40.0, 50)
    c = Counter(len(r.prompt) for r in reqs)
    assert c == {4: 300, 8: 100}


def test_gaps_follow_the_exponential_at_the_rate():
    mix = {"rate_per_s": 4.0, "max_total_tokens": 100, "pattern_seed": 1,
           "prompt_tokens": {"values": [4], "weights": [1]},
           "output_tokens": {"values": [2], "weights": [1]}}
    reqs = traffic.schedule(mix, 3, 500.0, 50)
    gaps = np.diff([r.due for r in reqs])
    assert abs(gaps.mean() - 0.25) < 0.01
    # an exponential's standard deviation equals its mean
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1


def test_seed_words_keep_every_bit():
    assert list(traffic.seed_words(2**40 + 1)) != \
        list(traffic.seed_words(1))
