"""The ``pangu_moe`` family's numbers, read from a configuration file:
the sizes as run (shared by the adapter and the plain reference, which
share nothing else) and what a decode step's latent attention has to do,
in operations and in bytes, which ``mla_latent_attn_roofline`` sets
against its device time.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """The sizes the program and the reference both run, under short
    names.  ``held`` counts the routed experts HELD here (``first`` ...
    ``first + held``), ``router_width`` the published experts the router
    scores; ``vocab`` is the slice of the vocabulary held here;
    ``layers`` the stage's layers, of which the first ``dense`` have a
    dense FFN.

    ``run.py --rehearse`` overlays GPT-2's key names (``n_embd``,
    ``n_head``, ``n_inner``, ``n_positions``) on any configuration: a
    file that carries them is the tiny preset, and every size the
    overlay does not name is set here beside those it does -- a dense
    layer and two expert layers, the published ratios of the rotary part
    to a head and of experts chosen to experts scored."""
    common = dict(top_k_scale=float(cfg["routed_scaling_factor"]),
                  theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
                  std=cfg["initializer_range"])
    if "n_embd" in cfg:
        return dict(
            common, vocab=cfg["vocab_size"], d=cfg["n_embd"], layers=3,
            dense=1, heads=4 * cfg["n_head"], nope=8, rope=4, v=8,
            q_rank=24, kv_rank=16, dense_width=cfg["n_inner"],
            expert_width=cfg["n_inner"] // 4, router_width=16, first=0,
            held=4, top_k=4, shared=1, init_layers=3)
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("latent attention has no grouped heads: "
                         "num_key_value_heads must equal num_attention_heads")
    return dict(
        common, vocab=cfg["vocab_size"], d=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        router_width=cfg["router_width"], first=cfg["experts_held_first"],
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"],
        init_layers=cfg["num_hidden_layers_published"])


def decode_latent_flops(cfg: dict, rows_live: float) -> float:
    """Operations of ONE decode step's latent attention over all the
    layers, for ``rows_live`` cached rows of live contexts (summed over
    the slots, one layer's): per row and head a score over ``kv_rank +
    rope`` values and a weighted sum over ``kv_rank``, a multiply and an
    add each.  The rows of dead slots and of positions past a context's
    end are not work the step has to do, whatever reads them."""
    z = sizes(cfg)
    per_row = 2 * z["heads"] * (z["kv_rank"] + z["rope"] + z["kv_rank"])
    return z["layers"] * per_row * rows_live


def decode_latent_bytes(cfg: dict, rows_live: float, slots: int) -> float:
    """Bytes the same attention has to move: every live row once
    (``kv_rank + rope`` bfloat16 values: one read can feed the scores and
    the weighted sum), and per slot and head the absorbed query in and
    the latent output back (bfloat16)."""
    z = sizes(cfg)
    row = 2 * (z["kv_rank"] + z["rope"])
    per_slot = 2 * z["heads"] * (z["kv_rank"] + z["rope"] + z["kv_rank"])
    return z["layers"] * (row * rows_live + per_slot * slots)
