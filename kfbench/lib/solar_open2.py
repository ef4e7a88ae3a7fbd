"""The ``solar_open2`` family's numbers, read from a configuration file:
the sizes as run (shared by the adapter and the plain reference, which
share nothing else) and what a decode step's KDA layers have to move, in
bytes, which ``kda_state_roofline`` sets against their device time.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """The sizes the program and the reference both run, under short
    names.  ``held`` counts the routed experts HELD here (``first`` ...
    ``first + held``), ``router_width`` the published experts the router
    scores; ``vocab`` is the slice of the vocabulary held here;
    ``layers`` the stage's layers, of which ``gqa_layers`` are softmax
    layers and the others KDA layers.

    ``run.py --rehearse`` overlays GPT-2's key names (``n_embd``,
    ``n_head``, ``n_inner``, ``n_positions``) on any configuration: a
    file that carries them is the tiny preset, and every size the
    overlay does not name is set here beside those it does -- one whole
    period of four layers, the published ratios of key/value to query
    heads and of experts chosen to experts scored."""
    common = dict(eps=cfg["rms_norm_eps"], std=cfg["initializer_range"],
                  top_k_scale=float(cfg["routed_scaling_factor"]),
                  taps=cfg["linear_attn_config"]["short_conv_kernel_size"])
    if "n_embd" in cfg:
        return dict(
            common, vocab=cfg["vocab_size"], d=cfg["n_embd"], layers=4,
            gqa_layers=(0,), heads=4 * cfg["n_head"], kv_heads=cfg["n_head"],
            head_dim=8, kda_heads=4, kda_dim=8, gate_rank=8,
            expert_width=cfg["n_inner"] // 8, router_width=16, first=0,
            held=4, top_k=4, shared=1, init_layers=4)
    lin = cfg["linear_attn_config"]
    return dict(
        common, vocab=cfg["vocab_size"], d=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"],
        # (the file keeps the published list; the stage held is the
        # model's first ``layers`` layers)
        gqa_layers=tuple(li for li in cfg["gqa_layers"]
                         if li < cfg["num_hidden_layers"]),
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], kda_heads=lin["num_heads"],
        kda_dim=lin["head_dim"], gate_rank=cfg["kda_gate_rank"],
        expert_width=cfg["moe_intermediate_size"],
        router_width=cfg["router_width"], first=cfg["experts_held_first"],
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"],
        init_layers=cfg["num_hidden_layers_published"])


def kda_layer_params(z: dict) -> int:
    """Parameters of one KDA layer's mixer."""
    d, c, r = z["d"], z["kda_heads"] * z["kda_dim"], z["gate_rank"]
    return (4 * d * c + 2 * (d * r + r * c) + d * z["kda_heads"]
            + 3 * c * z["taps"] + z["kda_heads"] + 2 * c + z["kda_dim"])


def decode_state_bytes(cfg: dict, slots_live: float) -> float:
    """Bytes ONE decode step's KDA layers have to move: the state (float32,
    ``heads x dim x dim`` a slot and layer) and the convolution tail of
    every LIVE slot read and written once, and the layers' weights
    (bfloat16) read once.  The states of dead slots are not work the
    step has to do, whatever reads them."""
    z = sizes(cfg)
    n_kda = z["layers"] - len(z["gqa_layers"])
    c = z["kda_heads"] * z["kda_dim"]
    slot = 4 * c * z["kda_dim"] + 2 * (z["taps"] - 1) * 3 * c
    return n_kda * (2 * slot * slots_live + 2 * kda_layer_params(z))
