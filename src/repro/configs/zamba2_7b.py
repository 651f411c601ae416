"""Zamba2-7B-Instruct, its first 24 layers — Mamba2 backbone with two
alternating shared attention blocks.

Source: huggingface.co/Zyphra/Zamba2-7B-Instruct, ``config.json``
(``model_type`` zamba2): hidden 3584; Mamba2 layers of 112 heads x 64,
``mamba_d_state`` 64, ``mamba_ngroups`` 2, conv 4, ``chunk_size`` 256;
``num_mem_blocks`` 2 shared blocks attending over the 7168-wide [h ‖
embedding] with 32 heads x 224 (32 KV heads), RoPE over the whole head,
scores scaled by (224 / 2) ** -1/2; a gated GELU MLP of 14336 with a
rank-128 LoRA adapter per invocation; vocab 32000, ``rms_norm_eps`` 1e-5.

Cut (the only departure from the published model): depth, to the first
24 of 81 layers, which keeps hybrid layers 6, 11, 17 and 23, i.e. each
shared block invoked twice.  Assumed: an output head of its own
(``config.json`` leaves ``tie_word_embeddings`` unset, and
``Zamba2Config`` ties by default; bench/configs/zamba2-7b.json says why
the benchmark's cell needs it untied).  Hydra++ heads draft a chain of 4
(recurrent state takes a chain, DESIGN.md §4).
"""
from repro.configs.base import DraftConfig, ModelConfig, SSMConfig, register

ZAMBA2_7B = register(ModelConfig(
    name="zamba2-7b",
    arch_type="hybrid",
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct",
    n_layers=24,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    rope_theta=10000.0,
    rms_eps=1e-5,
    tie_embeddings=False,
    block_kind="mamba2",
    hybrid_layer_ids=(6, 11, 17, 23),
    num_mem_blocks=2,
    adapter_rank=128,
    ssm=SSMConfig(d_state=64, expand=2, head_dim=64, conv_width=4,
                  chunk_size=256, n_groups=2),
    max_seq_len=4096,
    draft=DraftConfig(kind="hydra++", n_heads=4, n_mlp_layers=4,
                      prefix_attention=False),  # chain speculation
))
