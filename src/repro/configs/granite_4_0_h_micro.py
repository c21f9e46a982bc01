"""granite-4.0-h-micro [hybrid] — 40L d_model=2048: 36 Mamba-2 layers (64
heads of 64, state 128, one group, conv 4) and 4 NoPE GQA attention layers
(32 q / 8 kv heads of 64) at layers 5, 15, 25, 35; a dense SwiGLU MLP of
8192 after every mixer; RMSNorm eps 1e-5; vocab=100352, tied.
[hf:ibm-granite/granite-4.0-h-micro]

Like granite-moe-1b-a400m, the program leaves out the source's four muP
scalars (attention_multiplier 1/64 -> 1/sqrt(64), embedding_multiplier,
residual_multiplier, logits_scaling).
"""

from repro.configs.base import ModelConfig

# layer_types: [m, m, m, m, m, attention, m, m, m, m] four times
_PERIOD = ("mamba",) * 5 + ("global",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,  # shared_intermediate_size
    vocab=100352,
    activation="silu",
    norm="rmsnorm",
    rms_norm_eps=1e-5,
    position_embedding="nope",
    tie_embeddings=True,
    pattern=_PERIOD * 4,
    mamba_n_heads=64,
    mamba_d_head=64,
    mamba_d_state=128,
    mamba_d_conv=4,
    mamba_chunk_size=256,
)

# two periods of a short stack with both kinds
SMOKE = ModelConfig(
    name="granite-4.0-h-smoke",
    family="hybrid",
    n_layers=4,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab=256,
    activation="silu",
    norm="rmsnorm",
    rms_norm_eps=1e-5,
    position_embedding="nope",
    tie_embeddings=True,
    pattern=("mamba", "global") * 2,
    mamba_n_heads=8,
    mamba_d_head=16,
    mamba_d_state=16,
    mamba_d_conv=4,
    mamba_chunk_size=16,
    compute_dtype="float32",
)
