"""``pangu_moe`` family (openPangu-Ultra-MoE): what joins a
configuration file to the program.

``program_model`` builds the program's own model from the sizes as run
(``lib/pangu.py::sizes``: the published widths, the chip's share of the
experts and of the vocabulary, the stage's layers and the depth its
initialisation is reckoned from); ``init_params`` is that model's own
``init`` -- bfloat16 leaves, the routers and the norms' scales float32
-- so there is one initialisation and one tree, and the program and the
reference are both handed it.  Meant to run under one ``jax.jit`` so
that every leaf is made on the device in one call.
"""

from __future__ import annotations

from kfbench.lib.pangu import sizes


def program_model(cfg: dict):
    from kungfu_tpu.models.pangu_moe import PanguMoe, PanguMoeConfig

    z = sizes(cfg)
    return PanguMoe(PanguMoeConfig(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        init_layers=z["init_layers"], n_dense=z["dense"],
        n_heads=z["heads"], qk_nope_dim=z["nope"], qk_rope_dim=z["rope"],
        v_head_dim=z["v"], q_lora_rank=z["q_rank"],
        kv_lora_rank=z["kv_rank"], d_ff=z["dense_width"],
        d_expert=z["expert_width"], n_experts=z["router_width"],
        experts_held=(z["first"], z["held"]), top_k=z["top_k"],
        n_shared=z["shared"], routed_scale=z["top_k_scale"],
        rope_theta=z["theta"], norm_eps=z["eps"], init_std=z["std"],
        max_seq=cfg.get("n_positions", 16384), dtype="bfloat16"))


def init_params(cfg: dict, key):
    return program_model(cfg).init(key)


def check_tree(cfg: dict, params) -> None:
    """(The loop asks every family; these weights are the program's own
    ``init``, so there is no second tree to hold against it.)"""


def n_params(cfg: dict) -> int:
    z = sizes(cfg)
    d, h, r = z["d"], z["heads"], z["kv_rank"]
    attn = (d * z["q_rank"] + z["q_rank"] + z["q_rank"] * h * (
        z["nope"] + z["rope"]) + d * (r + z["rope"]) + r
        + h * r * (z["nope"] + z["v"]) + h * z["v"] * d)
    moe = (3 * d * z["expert_width"] * (z["held"] + z["shared"])
           + d * z["router_width"])
    return (2 * z["vocab"] * d + d + z["layers"] * (attn + 4 * d)
            + z["dense"] * 3 * d * z["dense_width"]
            + (z["layers"] - z["dense"]) * moe)
