"""Zamba2-2.7B: 54 Mamba-2 layers; before nine of them one of two shared
attention + MLP blocks reads the running stream concatenated with the
embedding [arXiv:2411.15242; https://huggingface.co/Zyphra/Zamba2-2.7B].
The paper's headline hybrid workload.

Widths as published: d 2560; Mamba-2 with 80 heads of 64, d_state 64;
shared attention over a 2d = 5120 input, 32 heads of 160; MLP 10240 with
a rank-128 LoRA per application; vocabulary 32000, the head tied."""
from repro.models.config import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="zamba2-2.7b", family="hybrid",
    n_layers=54, d_model=2560, n_heads=32, n_kv_heads=32, head_dim=160,
    d_ff=10240, vocab_size=32000,
    pattern=("mamba2",), ffn_kind="none", pos_emb="none",
    hybrid_layer_ids=(6, 12, 18, 24, 30, 36, 42, 47, 51),
    n_mem_blocks=2, adapter_rank=128, tie_embeddings=True,
    ssm=SSMConfig(d_state=64, head_dim=64, expand=2, d_conv=4, chunk=64),
)

# the same structure at toy widths: two alternating blocks over three
# applications at irregular gaps, the concatenated input, LoRA and linear
SMOKE = ModelConfig(
    name="zamba2-2.7b-smoke", family="hybrid",
    n_layers=7, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
    d_ff=128, vocab_size=512,
    pattern=("mamba2",), ffn_kind="none", pos_emb="none",
    hybrid_layer_ids=(2, 4, 5), n_mem_blocks=2, adapter_rank=8,
    tie_embeddings=True,
    ssm=SSMConfig(d_state=16, head_dim=16, expand=2, d_conv=4, chunk=16),
)
