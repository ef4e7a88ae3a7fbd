"""``phi4flash`` family (Phi-4-mini-flash-reasoning): what joins a
configuration file to the program.

``program_model`` builds the program's own model from the sizes as run
(``lib/phi4flash.py::sizes``: the published widths, window and depth,
the state-space layer's sizes the file assumes); ``init_params`` is that
model's own ``init`` -- bfloat16 leaves; the norms, ``a_log``, ``d``,
``b_dt`` and the ``lam`` vectors float32 -- so there is one
initialisation and one tree, and the program and the reference are both
handed it.  Meant to run under one ``jax.jit`` so that every leaf is
made on the device in one call.
"""

from __future__ import annotations

from kfbench.lib import phi4flash as lib


def program_model(cfg: dict):
    from kungfu_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig

    z = lib.sizes(cfg)
    return Phi4Flash(Phi4FlashConfig(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        n_heads=z["heads"], n_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
        d_ff=z["ffn"], window=z["window"], d_inner=z["inner"],
        d_state=z["state"], d_conv=z["taps"], dt_rank=z["dt_rank"],
        norm_eps=z["eps"], init_std=z["std"],
        max_seq=cfg.get("n_positions", 4096), dtype="bfloat16"))


def init_params(cfg: dict, key):
    return program_model(cfg).init(key)


def check_tree(cfg: dict, params) -> None:
    """(The loop asks every family; these weights are the program's own
    ``init``, so there is no second tree to hold against it.)"""


def n_params(cfg: dict) -> int:
    return lib.n_params(lib.sizes(cfg))
