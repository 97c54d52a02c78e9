"""granite-4.0-h-micro — Mamba2 + NoPE GQA hybrid, an MLP after every mixer.

[hf: ibm-granite/granite-4.0-h-micro config.json]  40L d_model=2048: a
period of 10 layers (5 Mamba2, 1 attention, 4 Mamba2) four times, each
layer followed by a SwiGLU MLP of 8192 (``shared_intermediate_size``).
Mamba2: 64 heads of 64, d_state 128, 1 group, conv 4 with bias, chunk 256,
no projection bias.  Attention: 32 query / 8 KV heads of 64, no position
embedding, score scale ``attention_multiplier`` 1/64.  Embedding x12,
every mixer and MLP output x0.22, logits / 8; tied embeddings, vocab
100352.
"""

from repro.models.config import ModelConfig, SSMConfig

ARCH_ID = "granite-4.0-h-micro"

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def config() -> ModelConfig:
    return ModelConfig(
        arch_id=ARCH_ID,
        family="hybrid",
        num_layers=40,
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        d_ff=8192,
        vocab_size=100352,
        activation="swiglu",
        tie_embeddings=True,
        rope_theta=10000.0,
        rms_eps=1e-5,
        ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, n_groups=1,
                      conv_kernel=4, chunk=256),
        layer_types=_PERIOD * 4,
        position_embedding="nope",
        attn_scale=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
    )


def smoke_config() -> ModelConfig:
    """Two periods of a shortened pattern (2 Mamba2, 1 attention, 1 Mamba2)
    at small widths; the scalings stay the published ones.  The vocabulary
    stays large against the width: with a narrow tied table the x12 input
    embedding outweighs the layers' outputs and the head repeats its input
    token whatever the layers compute."""
    return config().replace(
        num_layers=8, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
        vocab_size=8192,
        ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, n_groups=1,
                      conv_kernel=4, chunk=8),
        layer_types=("mamba", "mamba", "attention", "mamba") * 2,
        attn_scale=0.0625,
        remat=False)
