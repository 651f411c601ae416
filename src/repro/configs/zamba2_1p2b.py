"""Zamba2-1.2B — Mamba2 backbone + a shared attention block [arXiv:2411.15242].

The widths are the repo's registered ones.  Assumed, as the paper does
not give them here: the shared block runs before every sixth Mamba2
layer from the first (``hybrid_layer_ids``), one weight set
(``num_mem_blocks``), a rank-128 LoRA adapter on its MLP per invocation,
one B/C group, and tied embeddings; the block itself is Zamba2-7B's
(``models/model.py::hybrid_block_fwd``: a gated GELU MLP, scores scaled
by (head_dim / 2) ** -1/2).
"""
from repro.configs.base import DraftConfig, ModelConfig, SSMConfig, register

ZAMBA2_1P2B = register(ModelConfig(
    name="zamba2-1.2b",
    arch_type="hybrid",
    source="arXiv:2411.15242",
    n_layers=38,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=64,
    d_ff=8192,
    vocab_size=32000,
    tie_embeddings=True,
    block_kind="mamba2",
    hybrid_layer_ids=tuple(range(0, 38, 6)),
    num_mem_blocks=1,
    adapter_rank=128,
    ssm=SSMConfig(d_state=64, expand=2, head_dim=64, conv_width=4, chunk_size=64),
    max_seq_len=4096,
    draft=DraftConfig(kind="hydra++", n_heads=4, n_mlp_layers=4,
                      prefix_attention=False),  # chain speculation (see DESIGN §4)
))
