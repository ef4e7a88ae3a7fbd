"""``solar_open2`` family (Solar Open 2): what joins a configuration
file to the program.

``program_model`` builds the program's own model from the sizes as run
(``lib/solar_open2.py::sizes``: the published widths, the chip's share
of the experts and of the vocabulary, the stage's layers and the depth
its initialisation is reckoned from); ``init_params`` is that model's own
``init`` -- bfloat16 leaves; the routers, the norms' scales, ``a_log``
and ``b_dt`` float32 -- so there is one initialisation and one tree, and
the program and the reference are both handed it.  Meant to run under
one ``jax.jit`` so that every leaf is made on the device in one call.
"""

from __future__ import annotations

from kfbench.lib.solar_open2 import kda_layer_params, sizes


def program_model(cfg: dict):
    from kungfu_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config

    z = sizes(cfg)
    return SolarOpen2(SolarOpen2Config(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        init_layers=z["init_layers"], gqa_layers=z["gqa_layers"],
        n_heads=z["heads"], n_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
        kda_heads=z["kda_heads"], kda_head_dim=z["kda_dim"],
        conv_kernel=z["taps"], gate_rank=z["gate_rank"],
        d_expert=z["expert_width"], n_experts=z["router_width"],
        experts_held=(z["first"], z["held"]), top_k=z["top_k"],
        n_shared=z["shared"], routed_scale=z["top_k_scale"],
        norm_eps=z["eps"], init_std=z["std"],
        max_seq=cfg.get("n_positions", 4096), dtype="bfloat16"))


def init_params(cfg: dict, key):
    return program_model(cfg).init(key)


def check_tree(cfg: dict, params) -> None:
    """(The loop asks every family; these weights are the program's own
    ``init``, so there is no second tree to hold against it.)"""


def n_params(cfg: dict) -> int:
    z = sizes(cfg)
    d = z["d"]
    n_gqa = len(z["gqa_layers"])
    gqa = d * z["head_dim"] * (3 * z["heads"] + 2 * z["kv_heads"])
    moe = (3 * d * z["expert_width"] * (z["held"] + z["shared"])
           + d * z["router_width"])
    return (2 * z["vocab"] * d + d + n_gqa * gqa
            + (z["layers"] - n_gqa) * kda_layer_params(z)
            + z["layers"] * (moe + 2 * d))
