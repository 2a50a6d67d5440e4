"""qwen3-1.7b [hf:Qwen/Qwen3 family; hf]: qk_norm, GQA."""
from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-1.7b", family="dense",
    n_layers=28, d_model=2048, n_heads=16, n_kv_heads=8, head_dim=128,
    d_ff=6144, vocab=151936, qk_norm=True, rope_theta=1000000.0,
    norm_eps=1e-6, tie_embeddings=True,
    skip_shapes=("long_500k",),
)
