"""granite-4.0-h-micro [granite_hybrid]: 40L d_model=2048, 36 Mamba2 layers
(64 heads of 64, d_state 128, d_conv 4 with bias, n_groups 1) and 4 NoPE
GQA attention layers (32/8 heads of 64) at 5, 15, 25 and 35, each layer
with its own GLU MLP (8192); vocab 100352, tied embeddings; Granite
scalars embedding x12, attention 1/64, residual x0.22, logits /8.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid]

``CONFIG`` is the published model cut to its first 20 layers (two whole
periods, attention at 5 and 15): stage 1 of a two-stage pipeline, 20
layers a chip. ``layer_types`` keeps all 40 published entries; the model
runs the first ``n_layers``. Every width is as published."""

from ..models.config import ModelConfig

#: the published ``layer_types``: attention at 5, 15, 25, 35
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="granite_hybrid",
    n_layers=20,                # published 40; stage 1 of a 2-stage pipeline
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,                  # shared_intermediate_size
    vocab_size=100352,
    mlp_type="glu",
    act="silu",
    norm_eps=1e-5,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    conv_width=4,
    conv_bias=True,
    layer_types=LAYER_TYPES,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
)

SMOKE = ModelConfig(
    name="granite-4.0-h-micro-smoke",
    family="granite_hybrid",
    n_layers=10,                # one period: 5 Mamba, attention, 4 Mamba
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=512,
    mlp_type="glu",
    act="silu",
    ssm_state=16,
    ssm_expand=2,
    ssm_head_dim=16,
    ssm_chunk=8,
    conv_width=4,
    conv_bias=True,
    layer_types=LAYER_TYPES,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    dtype="float32",
)
