"""Model kind ``dense``: decoder layers of causal GQA attention and a
SiLU-gated MLP, as a configuration file's ``model`` states them
(``bench/configs/minitron-4b.json``).

The harness loads ``bench/kinds/<model.kind>.py`` for a configuration and
reads these four names from it:

    check_program(cfg, conf)   the program's registered configuration has
                               to be the file's; raises SystemExit if not
    logits_at(params, model, tokens, positions, quant=None, pad_to=0)
                               the plain float32 reference
                               (``harness.reference``); ``quant="fp8"`` is
                               the control
    flops_per_live_row(model, draft)
                               FLOPs of one live row of a verify step, for
                               ``step_mfu`` (``work.verify_step``)
    tree_work(cached, T_pad, model)
                               (flops, bytes) of one paged tree-kernel call
                               over rows with ``cached`` tokens each, for
                               ``tree_attn_roofline`` (``work.tree_attn``)
"""
from harness.reference import logits_at  # noqa: F401
from work import tree_attn
from work.verify_step import flops_per_live_row  # noqa: F401


def check_program(cfg, conf: dict) -> None:
    """The program's registered configuration has to be the file's."""
    m, d = conf["model"], conf["draft"]
    want = {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "resolved_head_dim": m["head_dim"], "d_ff": m["d_ff"],
            "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
            "rms_eps": m["rms_eps"], "tie_embeddings": m["tie_embeddings"],
            "dtype": conf["dtype"]}
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise SystemExit(f"program config {cfg.name}.{k}="
                             f"{getattr(cfg, k)!r}, file says {v!r}")
    dc = cfg.draft
    got = (dc.kind, dc.n_heads, dc.n_mlp_layers, dc.prefix_attention)
    if got != (d["kind"], d["n_heads"], d["n_mlp_layers"],
               d["prefix_attention"]):
        raise SystemExit(f"program draft {got} != file {d}")


def tree_work(cached, T_pad: int, model: dict):
    return tree_attn.work(cached, T_pad, model["n_heads"],
                          model["n_kv_heads"], model["head_dim"])
