"""``evabyte`` family (EvaByte): what joins a configuration file to the
program.

``program_model`` builds the program's own model from the sizes as run
(``lib/evabyte.py::sizes``: the published widths, chunk and window, the
stage's layers and the depth its initialisation is reckoned from);
``init_params`` is that model's own ``init`` -- bfloat16 leaves, the
norms' offsets float32 -- so there is one initialisation and one tree,
and the program and the reference are both handed it.  Meant to run
under one ``jax.jit`` so that every leaf is made on the device in one
call.
"""

from __future__ import annotations

from kfbench.lib.evabyte import layer_params, sizes


def program_model(cfg: dict):
    from kungfu_tpu.models.evabyte import EvaByte, EvaByteConfig

    z = sizes(cfg)
    return EvaByte(EvaByteConfig(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        init_layers=z["init_layers"], n_heads=z["heads"],
        head_dim=z["head_dim"], d_ff=z["ffn"], chunk_size=z["chunk"],
        window_size=z["window"], n_pred_heads=z["pred_heads"],
        rope_theta=z["theta"], norm_eps=z["eps"], init_std=z["std"],
        max_seq=cfg.get("n_positions", cfg["max_position_embeddings"]),
        dtype="bfloat16"))


def init_params(cfg: dict, key):
    return program_model(cfg).init(key)


def check_tree(cfg: dict, params) -> None:
    """(The loop asks every family; these weights are the program's own
    ``init``, so there is no second tree to hold against it.)"""


def n_params(cfg: dict) -> int:
    z = sizes(cfg)
    d = z["d"]
    return (z["layers"] * layer_params(z) + z["vocab"] * d
            + d * z["pred_heads"] * z["vocab"] + d)
