"""Operations of one verify step, counted from the configuration's shapes.

Used by ``step_mfu``: the FLOPs a step does for each live row, a lower
bound of what the program computes (attention over the cache, the
softmaxes and the norms are left out; the rows of empty slots are not
counted).  Nothing here reads ``ModelConfig.n_params`` or any other number
the program computes.
"""
from __future__ import annotations


def _dense_layer_params(m: dict) -> int:
    d, D = m["d_model"], m["head_dim"]
    attn = d * m["n_heads"] * D * 2 + d * m["n_kv_heads"] * D * 2
    return attn + 3 * d * m["d_ff"] + 2 * d


def base_params(m: dict) -> int:
    """Parameters of the base model, from the shapes."""
    d, V = m["d_model"], m["vocab_size"]
    emb = V * d * (1 if m["tie_embeddings"] else 2) + d
    return emb + m["n_layers"] * _dense_layer_params(m)


def base_matmul_params_per_token(m: dict) -> int:
    """Weights each token multiplies through: every layer's matrices and
    the output head; the embedding is a lookup."""
    d, V = m["d_model"], m["vocab_size"]
    return m["n_layers"] * (_dense_layer_params(m) - 2 * d) + d * V


def draft_flops_per_row(m: dict, draft: dict) -> int:
    """Each node at depth k >= 1 runs head k-1 once: its input projection
    over k + 1 embeddings, its residual layers and the unembedding."""
    d, V = m["d_model"], m["vocab_size"]
    total = 0
    for k, nodes in enumerate(draft["tree_nodes_per_depth"]):
        if k == 0:
            continue
        head = (k + 1) * d * d + (draft["n_mlp_layers"] - 1) * d * d + d * V
        total += nodes * 2 * head
    if draft["prefix_attention"]:
        total += 2 * (_dense_layer_params(m) - 2 * d)   # one token at least
    return total


def tree_tokens(draft: dict) -> int:
    return sum(draft["tree_nodes_per_depth"])


def flops_per_live_row(m: dict, draft: dict) -> int:
    return (2 * tree_tokens(draft) * base_matmul_params_per_token(m)
            + draft_flops_per_row(m, draft))
