"""Mellum2-12B-A2.5B  [hf:JetBrains/Mellum2-12B-A2.5B-Instruct, config.json].

A code model of 28 layers, every one sparse: 64 SwiGLU experts of width
896, top-8 of a softmax router with the top-8 weights renormalized
(``norm_topk_prob``), no shared expert.  GQA with 32 query and 4 kv heads
of 128, no attention bias, RMSNorm (eps 1e-6), an untied head over 98,304
ids.  ``layer_types`` repeat sliding, sliding, sliding, full with a
sliding window of 1024; the sliding layers use plain RoPE (theta 5e5), the
full layers YaRN (theta 5e5, factor 16 over 8,192 original positions,
beta_fast 32, beta_slow 1, attention factor 1.27726).

``d_ff`` is the expert width (the published ``moe_intermediate_size``,
896; ``moe.d_expert`` is left 0, so the layer reads it there).  The published ``intermediate_size`` (7168) is the width of a dense
MLP layer, and the model has none.  The card mentions a multi-token
prediction head; the released config declares no next-token layers, so
none is built.  No q/k norm and no router bias: the config names neither.
The load-balance term is off (``aux_loss_coef`` 0): the config names none.
"""
from repro.configs.base import ArchConfig, MoEConfig, YarnConfig

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_ff=896,
    vocab=98304,
    head_dim=128,
    act="silu_gated",
    rope_theta=500_000.0,
    yarn=YarnConfig(factor=16.0, original_max=8192, beta_fast=32.0,
                    beta_slow=1.0, attention_factor=1.2772588722239782),
    norm="rmsnorm",
    moe=MoEConfig(n_experts=64, top_k=8, router="softmax",
                  norm_topk=True, aux_loss_coef=0.0),
    window=1024,
    window_pattern=4,       # layers 3, 7, ..., 27 are full
    window_native=True,
    max_seq=131072,
    # the dropless expert layer's buffers hold every token's 8 picks: a
    # train step at 8 rows of 513 keeps 17.6 GB live if each layer's
    # activations stay for the backward (memory_analysis, described v5e,
    # 4 layers), 10.5 GB if each layer is recomputed from its input
    remat_layers=True,
).validate()


def smoke() -> ArchConfig:
    """Two layers (one sliding, one full), 4 experts of which 2 per
    token, a window of 16: prompts past it tell the layer types apart."""
    return CONFIG.replace(
        n_layers=2, d_model=256, n_heads=8, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab=512, max_seq=256, window=16, window_pattern=2,
        moe=MoEConfig(n_experts=4, top_k=2, router="softmax",
                      norm_topk=True, aux_loss_coef=0.0),
    ).validate()
