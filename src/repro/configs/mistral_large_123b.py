"""mistral-large-123b [dense] — [hf:mistralai/Mistral-Large-Instruct-2407]:
88L d_model=12288 96H (GQA kv=8) head_dim=128 d_ff=28672 (SwiGLU)
vocab=32768 rope_theta=1e6 RMSNorm eps=1e-5, untied embeddings, no sliding
window (config.json: ``sliding_window: null``)."""

from repro.configs.base import ModelConfig, smoke_reduce

CONFIG = ModelConfig(
    arch_id="mistral-large-123b",
    family="dense",
    source="hf:mistralai/Mistral-Large-Instruct-2407",
    num_layers=88,
    d_model=12288,
    n_heads=96,
    n_kv_heads=8,
    head_dim=128,
    d_ff=28672,
    vocab_size=32768,
    rope_theta=1_000_000.0,
    norm_eps=1e-5,
    activation="silu",
    mlp_gated=True,
)


def smoke_config():
    return smoke_reduce(CONFIG)
