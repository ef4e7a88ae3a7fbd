"""``cohere2_moe`` family (Command A+): what joins a configuration file
to the program.

``program_model`` builds the program's own model from the sizes as run
(``lib/cohere2.py::sizes``: the published widths, the chip's share of
the experts and of the vocabulary, the stage's layers and the depth its
initialisation is reckoned from); ``init_params`` is that model's own
``init`` -- bfloat16 leaves, the routers and the LayerNorm scales
float32 -- so there is one initialisation and one tree, and the program
and the reference are both handed it.  Meant to run under one ``jax.jit``
so that every leaf is made on the device in one call.
"""

from __future__ import annotations

from kfbench.lib.cohere2 import sizes


def program_model(cfg: dict):
    from kungfu_tpu.models.cohere2_moe import Cohere2Moe, Cohere2MoeConfig

    z = sizes(cfg)
    return Cohere2Moe(Cohere2MoeConfig(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        init_layers=z["init_layers"], n_heads=z["heads"],
        n_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
        d_expert=z["expert_width"], n_experts=z["router_width"],
        experts_held=(z["first"], z["held"]), top_k=z["top_k"],
        n_shared=z["shared"], window=z["window"], period=z["period"],
        rope_theta=z["theta"], norm_eps=z["eps"],
        logit_scale=z["logit_scale"], init_std=z["std"],
        max_seq=cfg.get("n_positions", 8192), dtype="bfloat16"))


def init_params(cfg: dict, key):
    return program_model(cfg).init(key)


def check_tree(cfg: dict, params) -> None:
    """(The loop asks every family; these weights are the program's own
    ``init``, so there is no second tree to hold against it.)"""


def n_params(cfg: dict) -> int:
    z = sizes(cfg)
    d, f = z["d"], z["expert_width"]
    attn = 2 * d * z["head_dim"] * (z["heads"] + z["kv_heads"])
    layer = (attn + 3 * d * f * (z["held"] + z["shared"])
             + d * z["router_width"] + d)
    return z["vocab"] * d + z["layers"] * layer + d
