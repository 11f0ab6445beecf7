"""The float32 reference agrees with the program's own model, and the
family's weights are the tree the program reads, bit for bit per layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, spec

CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]


def _smoke(name):
    return model.flat(spec.config(name), rehearse=True)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_tree_is_the_programs(name):
    from repro.models import build_model

    m = _smoke(name)
    fam = model.family(m)
    ours = jax.eval_shape(lambda: fam.make(fam.Dims.of(m), 0))
    theirs = build_model(fam.program_config(m)).param_shapes()
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("name", CONFIGS)
def test_one_layer_rebuilds_bit_for_bit(name):
    m = _smoke(name)
    fam = model.family(m)
    dm = fam.Dims.of(m)
    full = fam.make(dm, 2**31 + 5)
    for l in range(dm.layers):
        one = fam.layer(dm, 2**31 + 5, l)
        stacked = jax.tree.map(lambda a: a[l], full["stack"]["pos0"])
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out = fam.outer(dm, 2**31 + 5)
    np.testing.assert_array_equal(np.asarray(out["embed"]["tok"]),
                                  np.asarray(full["embed"]["tok"]))


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_the_programs_forward(name):
    """Same weights, both in float32: the program's prefill logits at the
    last position of several prefixes equal the reference's."""
    from repro.models import build_model

    m = _smoke(name)
    fam = model.family(m)
    dm = fam.Dims.of(m)
    seed = 7
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          fam.make(dm, seed))
    prog = build_model(fam.program_config(m))
    rng = np.random.default_rng(0)
    S = 24
    toks = rng.integers(1, m["vocab_size"], size=(4, S)).astype(np.int32)
    ref = spec.reference(m["reference"])
    targets = np.tile(np.arange(S, dtype=np.int32) % m["vocab_size"],
                      (4, 1))
    best, at_t, arg = ref.forward(dm, m, seed, toks, targets)
    for n in (5, 13, S):
        logits, _ = prog.prefill(params, {"tokens": jnp.asarray(toks[:, :n])})
        logits = np.asarray(logits)
        np.testing.assert_allclose(logits.max(-1), best[:, n - 1],
                                   rtol=1e-4, atol=1e-4)
        picked = logits[np.arange(4), targets[:, n - 1]]
        np.testing.assert_allclose(picked, at_t[:, n - 1], rtol=1e-4,
                                   atol=1e-4)
        assert (logits.argmax(-1) == arg[:, n - 1]).all()


@pytest.mark.parametrize("name", CONFIGS)
def test_float8_control_departs_from_the_reference(name):
    m = _smoke(name)
    dm = model.family(m).Dims.of(m)
    toks = np.random.default_rng(1).integers(
        1, m["vocab_size"], size=(4, 16)).astype(np.int32)
    tg = np.zeros_like(toks)
    ref = spec.reference(m["reference"])
    b32, _, a32 = ref.forward(dm, m, 3, toks, tg)
    b8, _, a8 = ref.forward(dm, m, 3, toks, tg, quant=True)
    assert np.abs(b8 - b32).max() > 1e-3
    assert np.isfinite(b8).all()
