"""Work counts against hand counts on small shapes, and the peaks table:
the shared counts (``bench.work``) and the dense family's
(``bench/families/dense.py``)."""

import pytest

from bench import spec, work

dense = spec.family("dense")

M = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
     "head_dim": 2, "intermediate_size": 16, "vocab_size": 10,
     "num_hidden_layers": 3, "norm_kind": "rmsnorm"}


def test_layer_params_by_hand():
    # wq 8*4*2 + wk,wv 2*(8*2*2) + wo 4*2*8 + mlp 3*8*16
    assert dense.layer_matmul_params(M) == 64 + 64 + 64 + 384


def test_weight_bytes_count_norms_and_unembedding():
    per_layer = 576
    norms = (2 * 3 + 1) * 8
    assert dense.weight_bytes(M) == 2 * (3 * per_layer + 80 + norms)
    assert dense.weight_bytes(dict(M, norm_kind="layernorm_np")) == \
        2 * (3 * per_layer + 80)


def test_kv_and_attention_by_hand():
    assert work.kv_token_bytes(M) == 2 * 2 * 3 * 2 * 2     # 48
    # 4 heads x 2 dims x 5 live x 2 (q.K, p.V) x 2 FLOPs x 3 layers
    assert work.attn_flops(M, 5) == 480


def test_decode_window_by_hand():
    flops, nbytes = dense.decode_window(M, steps=2, lives=[5, 7])
    mm = 3 * 576 + 80
    assert flops == 2 * (2 * mm) + 480 + 672
    assert nbytes == 2 * dense.weight_bytes(M) + (5 * 48 + 48) + (7 * 48 + 48)


def test_paged_attention_by_hand():
    flops, nbytes = work.paged_attention(M, [5])
    assert flops == 480
    # live K/V, plus q read and output written: 2 x 2 B x 3 layers x 4 x 2
    assert nbytes == 5 * 48 + 96


def test_prefill_by_hand():
    # 3 positions: matmuls 2*3*3*576, causal 6 pairs x 4*4*2 x 3 layers,
    # last-position unembedding 2*80
    assert dense.prefill_flops(M, 3) == 2 * 3 * 3 * 576 + 6 * 32 * 3 + 160


def test_least_time_names_its_bound():
    pk = {"bf16_flop_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 50, pk) == (10.0, "compute")
    assert work.least_seconds(100, 50, pk) == (5.0, "memory")


def test_peaks_table_refuses_unknown_kinds():
    assert work.peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
