"""FLOP and byte counts against hand counts, and parameter counts from
shapes against the program's own tree (never ``ModelConfig.n_params``)."""
import json
import os

import jax
import numpy as np
import pytest

from work import tree_attn, verify_step

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_tree_attn_hand_count():
    # 2 live rows with 10 and 20 cached tokens, T=8, Hq=4, Hkv=2, D=16
    flops, nbytes = tree_attn.work([10, 20], 8, 4, 2, 16)
    assert flops == 4 * 4 * 8 * 16 * 30
    assert nbytes == 2 * (2 * 2 * 16 * 30 + 2 * 8 * 16 * (2 * 4 + 2 * 2))


def test_verify_step_hand_count_tiny_dense():
    m = {"kind": "dense", "n_layers": 1, "d_model": 4, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 2, "d_ff": 8, "vocab_size": 10,
         "tie_embeddings": False}
    d = {"tree_nodes_per_depth": [1, 2], "n_mlp_layers": 2,
         "prefix_attention": False}
    # layer matrices: wq 4x4, wo 4x4, wk 4x2, wv 4x2, mlp 3 x 4x8 -> 144
    # per token with the head 4x10: 184
    assert verify_step.base_matmul_params_per_token(m) == 184
    # 3 tree tokens; 2 depth-1 nodes each: w_in 8x4, one residual 4x4,
    # unembedding 4x10 -> 88 MACs
    assert verify_step.flops_per_live_row(m, d) == 2 * 3 * 184 + 2 * 2 * 88


@pytest.mark.parametrize("name,want", [("minitron-4b", 5_096_279_040)])
def test_base_params_from_shapes(name, want):
    from repro.configs import get_config
    from repro.models.model import init_params
    c = conf(name)
    got = verify_step.base_params(c["model"])
    assert got == want
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                get_config(name)))
    assert got == sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


@pytest.mark.parametrize("name", ["minitron-4b"])
def test_tree_shape_matches_program(name):
    from repro.configs import get_config
    from repro.launch.specs import tree_for
    tree = tree_for(get_config(name))
    assert np.bincount(tree.depth).tolist() == \
        conf(name)["draft"]["tree_nodes_per_depth"]
