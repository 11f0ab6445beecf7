"""yi-9b — llama-architecture GQA [arXiv:2403.04652].

48 layers, d_model 4096, 32 heads (GQA kv=4, head_dim 128), d_ff 11008,
vocab 64000. Full attention ⇒ long_500k skipped.
"""

from dataclasses import replace

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="yi-9b",
    family="dense",
    num_layers=48,
    d_model=4096,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=11008,
    vocab_size=64000,
    rope_kind="rope",
    rope_theta=5_000_000.0,
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    mlp_kind="swiglu",
    tie_embeddings=False,
    max_seq_len=32768,
    sub_quadratic=False,
)


def smoke() -> ModelConfig:
    return replace(
        CONFIG, name="yi9b-smoke", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
    )


# -- one chip's serving stage ------------------------------------------------
# The deployment: yi-9b served by a two-stage pipeline, one TPU v5e
# (16 GB HBM) per stage. This preset is one stage at published widths:
# 24 of the 48 layers, with the embedding and the unembedding both kept,
# so ~9.35 GB of bf16 parameters (``param_count``). The KV pool fills the
# rest of the chip. One token costs 48 KiB of KV at 24 layers (K and V,
# 4 KV heads × 128 × bf16), one 16-token page 768 KiB, so 3,328 pages
# are 2.62 GB. The decode step does not donate the pools and the prefill
# write copies them, so a pool is held twice at peak: the compiled step
# for v5e (``memory_analysis``) takes 11.97 GB of arguments, 2.62 GB of
# outputs and 0.24 GB of temporaries at 8 sequences of up to 64 pages
# (1,024 tokens) — about 88% of the chip.
SERVE_STAGE_LAYERS = 24
SERVE_STAGE_PAGES = 3328
SERVE_STAGE_PAGE_TOKENS = 16
SERVE_STAGE_MAX_PAGES_PER_SEQ = 64
SERVE_STAGE_MAX_ACTIVE = 8


def serve_stage():
    """(model config, pool config, max_active) of one chip's stage."""
    from ..serving.kv_pool import PoolConfig

    cfg = replace(CONFIG, name="yi-9b-stage", num_layers=SERVE_STAGE_LAYERS)
    pool = PoolConfig(num_pages=SERVE_STAGE_PAGES,
                      page_tokens=SERVE_STAGE_PAGE_TOKENS,
                      max_pages_per_seq=SERVE_STAGE_MAX_PAGES_PER_SEQ)
    return cfg, pool, SERVE_STAGE_MAX_ACTIVE
