"""A configuration's layer stack is a family module found by its name
(``bench/families/<family>.py``): the dense family gives what the harness
gave before families existed, bit for bit, and a family the harness has
never seen drives it by being added as a file."""

import hashlib
import json
import re
import shutil

import jax
import numpy as np
import pytest

from bench import model, spec
from bench.client import Record
from bench.measure import Run
from bench.trace import Span, Trace
from bench.traffic import Request

CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]
SEED = 2**31 + 5

# the program's ModelConfig of each committed configuration, as the
# harness built it before families, fields that differ from the defaults
PROGRAM = {
    "yi-9b-stage": dict(
        name="yi-9b-stage", family="dense", num_layers=24, d_model=4096,
        num_heads=32, num_kv_heads=4, head_dim=128, d_ff=11008,
        vocab_size=64000, rope_kind="rope", rope_theta=5000000.0,
        norm_kind="rmsnorm", norm_eps=1e-05, mlp_kind="swiglu",
        tie_embeddings=False, max_seq_len=1024),
    "olmo-1b": dict(
        name="olmo-1b", family="dense", num_layers=16, d_model=2048,
        num_heads=16, num_kv_heads=16, head_dim=128, d_ff=8192,
        vocab_size=50304, rope_kind="rope", rope_theta=10000.0,
        norm_kind="layernorm_np", norm_eps=1e-05, mlp_kind="swiglu",
        tie_embeddings=True, max_seq_len=2048),
}

# sha256 over (path, bytes) of every leaf at rehearsal size, seed SEED:
# ``make``'s tree, then each ``layer`` and ``outer`` (the reference's
# path), as the builder drew them before families
DIGESTS = {
    "yi-9b-stage": (
        "01ec75ff1cf5316d560b3b8c8d51c9115a279a2a7d22174662416f98114e6e0a",
        "6452780f1bcdf068664c15d5c468bafa3c5b861d9c82313a46454aca3cc575de"),
    "olmo-1b": (
        "ef4a6af6a76a0eccb2e8c765ce978ec3c9ce6d45f606f80ceecba1c85d6d5bc5",
        "a54c62a0fb2ded0852e3c377be85529bc863484009eb5e9b68a1c24abadc698f"),
}

# leaf shapes at published size, as the builder laid them out before
SHAPES = {
    "yi-9b-stage": {
        "['embed']['tok']": (64000, 4096),
        "['embed']['unembed']": (4096, 64000),
        "['norm_f']['scale']": (4096,),
        "['stack']['pos0']['attn']['wk']": (24, 4096, 4, 128),
        "['stack']['pos0']['attn']['wo']": (24, 32, 128, 4096),
        "['stack']['pos0']['attn']['wq']": (24, 4096, 32, 128),
        "['stack']['pos0']['attn']['wv']": (24, 4096, 4, 128),
        "['stack']['pos0']['mlp']['wi_gate']": (24, 4096, 11008),
        "['stack']['pos0']['mlp']['wi_up']": (24, 4096, 11008),
        "['stack']['pos0']['mlp']['wo']": (24, 11008, 4096),
        "['stack']['pos0']['norm_in']['scale']": (24, 4096),
        "['stack']['pos0']['norm_mlp']['scale']": (24, 4096),
    },
    "olmo-1b": {
        "['embed']['tok']": (50304, 2048),
        "['stack']['pos0']['attn']['wk']": (16, 2048, 16, 128),
        "['stack']['pos0']['attn']['wo']": (16, 16, 128, 2048),
        "['stack']['pos0']['attn']['wq']": (16, 2048, 16, 128),
        "['stack']['pos0']['attn']['wv']": (16, 2048, 16, 128),
        "['stack']['pos0']['mlp']['wi_gate']": (16, 2048, 8192),
        "['stack']['pos0']['mlp']['wi_up']": (16, 2048, 8192),
        "['stack']['pos0']['mlp']['wo']": (16, 8192, 2048),
    },
}

# (decode_window(m, 3, [100, 517, 1024]), prefill_flops at 32, 256, 768)
# at published size, as bench/work.py counted them before families
WORK = {
    "yi-9b-stage": ((27132297216.0, 26569039872.0),
                    (266483007488, 2139468333056, 6494665834496)),
    "olmo-1b": ((7275675648.0, 7276068864.0),
                (68994727936, 554273603584, 1688178524160)),
}


def _published(name):
    return model.flat(spec.config(name))


def _digest(*trees):
    h = hashlib.sha256()
    for tree in trees:
        for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
            h.update(jax.tree_util.keystr(path).encode())
            h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_a_file_without_family_is_dense(name):
    cfg = spec.config(name)
    assert "family" not in cfg
    assert model.family(cfg) is spec.family("dense")
    assert _published(name)["family"] == "dense"


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_is_the_one_built_before(name):
    from repro.models.config import ModelConfig

    m = _published(name)
    assert model.family(m).program_config(m) == ModelConfig(**PROGRAM[name])


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_bits_drawn_before(name):
    m = model.flat(spec.config(name), rehearse=True)
    fam = model.family(m)
    dm = fam.Dims.of(m)
    layers = [fam.layer(dm, SEED, l) for l in range(dm.layers)]
    assert (_digest(fam.make(dm, SEED)),
            _digest(*layers, fam.outer(dm, SEED))) == DIGESTS[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_published_shapes_are_the_ones_laid_out_before(name):
    m = _published(name)
    fam = model.family(m)
    tree = jax.eval_shape(lambda: fam.make(fam.Dims.of(m), 0))
    got = {jax.tree_util.keystr(p): a.shape
           for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == SHAPES[name]
    assert {str(a.dtype) for a in jax.tree.leaves(tree)} == {"bfloat16"}


def _run(m, lives=(), program=()):
    """A run whose window received one decode token per entry of
    ``lives`` (its live length), with ``program`` spans in its trace."""
    recs = []
    for i, n in enumerate(lives):
        r = Record(Request(i, 0.0, [1] * (n - 1), 2))
        r.due, r.times, r.done = 0.0, [0.1, 0.2], True
        recs.append(r)
    t = Trace(window=(0.0, 1e9), devices=1)
    t.program = list(program)
    return Run(m=m, records=recs, t0=0.0, end=1.0, setup_s=1.0, trace=t)


@pytest.mark.parametrize("name", CONFIGS)
def test_work_counts_are_the_ones_counted_before(name):
    m = _published(name)
    fam = model.family(m)
    decode, prefill = WORK[name]
    run = _run(m, [100, 517, 1024])
    assert sorted(run.decode_lives()) == [100, 517, 1024]
    assert fam.decode_work(run, 3) == decode
    assert tuple(fam.prefill_flops(m, p) for p in (32, 256, 768)) == prefill
    assert fam.decode_work(_run(m), 3) is None


# -- a family the harness has never seen ------------------------------------
TOY = '''"""A toy family with experts: a decode step reads only the experts
its tokens touched, which the program reports on ``serve.step``."""

from dataclasses import dataclass
from functools import partial

import jax

from bench import weights, work

KEYS = ("num_hidden_layers", "hidden_size", "num_experts",
        "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "vocab_size")


def program_config(m):
    return ("toy", m["num_experts"])


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    experts: int
    f: int

    @classmethod
    def of(cls, m):
        return cls(m["num_hidden_layers"], m["hidden_size"],
                   m["num_experts"], m["moe_intermediate_size"])


def _layer(dm, root, l):
    return {"experts": weights.normal(weights.leaf_key(root, 1, l),
                                      (dm.experts, dm.d, dm.f), 1.0)}


@partial(jax.jit, static_argnums=0)
def _make(dm, words):
    root = weights.root(words)
    return {"stack": jax.vmap(lambda l: _layer(dm, root, l))(
        jax.numpy.arange(dm.layers))}


def make(dm, seed):
    return _make(dm, weights.words(seed))


def layer(dm, seed, l):
    return _layer(dm, weights.root(weights.words(seed)), l)


def outer(dm, seed):
    return {}


def decode_work(run, steps):
    touched = sum(s.stats["experts_touched"]
                  for s in run.program("serve.step")
                  if s.name == "serve.step")
    if not touched:
        return None
    m = run.m
    nbytes = work.BYTES * touched * m["hidden_size"] \\
        * m["moe_intermediate_size"]
    return float(2 * nbytes), float(nbytes)


def prefill_flops(m, prompt):
    return 7 * prompt
'''

TOY_CONFIG = {
    "name": "toy-moe", "family": "toy", "reference": "toy",
    "num_hidden_layers": 2, "hidden_size": 8, "num_experts": 4,
    "moe_intermediate_size": 3, "num_attention_heads": 2,
    "num_key_value_heads": 1, "vocab_size": 16, "unused_key": 1,
    "program": {"head_dim": 4}, "serving": {"pool_pages": 9},
}


@pytest.fixture
def toy_bench(tmp_path, monkeypatch):
    """A copy of ``bench/`` with the toy family and its configuration
    added as files, and nothing else changed."""
    root = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "families" / "toy.py").write_text(TOY)
    (root / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    monkeypatch.setattr(spec, "BENCH_DIR", root)
    return root


def test_an_added_family_drives_flat_weights_and_work(toy_bench):
    cfg = spec.config("toy-moe")
    m = model.flat(cfg)
    assert "unused_key" not in m and m["num_experts"] == 4
    assert m["family"] == "toy" and m["head_dim"] == 4
    fam = model.family(m)
    assert fam.program_config(m) == ("toy", 4)
    dm = fam.Dims.of(m)
    tree = fam.make(dm, SEED)
    assert tree["stack"]["experts"].shape == (2, 4, 8, 3)
    np.testing.assert_array_equal(
        np.asarray(fam.layer(dm, SEED, 1)["experts"]),
        np.asarray(tree["stack"]["experts"][1]))
    # work counts read the program's counter on serve.step: 3 + 5 experts
    # touched, 2 B x 8 x 3 each
    steps = [Span("serve.step", 0.1e9, 0.2e9, "h#1", {"experts_touched": 3}),
             Span("serve.step.wait", 0.1e9, 0.2e9, "h#1", {}),
             Span("serve.step", 0.3e9, 0.4e9, "h#1", {"experts_touched": 5})]
    run = _run(m, [10], steps)
    run.trace.modules = [("jit_paged_decode_step(1)", 0.1e9, 0.2e9),
                         ("jit_paged_decode_step(1)", 0.3e9, 0.4e9)]
    run.peaks = {"bf16_flop_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert fam.decode_work(run, 2) == (768.0, 384.0)
    # the harness's own reader takes the family's count: 0.768 s of
    # least time over 0.2 s of steps
    assert spec.reader("decode_mfu").read(run) == pytest.approx(384.0)
    assert fam.prefill_flops(m, 5) == 35


def test_only_the_dense_family_and_reference_know_the_dense_stack():
    """No other file of the harness names a dense leaf, builds the
    program's ``ModelConfig`` or reads the dense MLP's width."""
    word = re.compile(r"wi_gate|wi_up|mlp_wo|ModelConfig|intermediate_size")
    own = {spec.BENCH_DIR / "families" / "dense.py",
           spec.BENCH_DIR / "reference" / "dense.py"}
    found = [str(p.relative_to(spec.ROOT))
             for p in sorted(spec.BENCH_DIR.rglob("*.py"))
             if p not in own and word.search(p.read_text())]
    assert found == []
