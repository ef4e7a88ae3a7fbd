"""The ``cohere2_moe`` family's numbers, read from a configuration file:
the sizes as run (shared by the adapter and the plain reference, which
share nothing else) and the bytes a decode step's routed product has to
read, which ``moe_experts_roofline`` sets against its device time.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """The sizes the program and the reference both run, under short
    names.  ``num_experts`` counts the experts HELD here (``first`` ...
    ``first + held``), ``router_width`` the published experts the router
    scores; ``vocab`` is the slice of the vocabulary held here.

    ``run.py --rehearse`` overlays GPT-2's key names (``n_embd``,
    ``n_head``, ``n_inner``, ``n_positions``) on any configuration: a
    file that carries them is the tiny preset, and every size the
    overlay does not name is set here beside those it does -- one period
    of layers, the published ratios of query to key/value heads and of
    experts chosen to experts scored."""
    if "n_embd" in cfg:
        g = cfg["n_head"]
        return dict(
            vocab=cfg["vocab_size"], d=cfg["n_embd"], layers=4, heads=8 * g,
            kv_heads=g, head_dim=8, expert_width=cfg["n_inner"] // 4,
            router_width=16, first=0, held=4, top_k=4, shared=2,
            window=cfg["n_positions"] // 8, period=4,
            theta=float(cfg["rope_theta"]), eps=cfg["layer_norm_eps"],
            logit_scale=float(cfg["logit_scale"]),
            std=cfg["initializer_range"], init_layers=4)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    period = kinds.index("full_attention") + 1
    if any((k == "full_attention") != ((i + 1) % period == 0)
           for i, k in enumerate(kinds)):
        raise ValueError("layer_types is not a period of sliding layers "
                         "closed by a full one")
    return dict(
        vocab=cfg["vocab_size"], d=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_width=cfg["intermediate_size"],
        router_width=cfg["router_width"], first=cfg["experts_held_first"],
        held=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        shared=cfg["num_shared_experts"], window=cfg["sliding_window"],
        period=period, theta=float(cfg["rope_theta"]),
        eps=cfg["layer_norm_eps"], logit_scale=float(cfg["logit_scale"]),
        std=cfg["initializer_range"],
        init_layers=cfg["num_hidden_layers_published"])


def decode_expert_bytes(cfg: dict, slots: int) -> int:
    """What the routed product of ONE decode step reads and writes, over
    all the layers: every held expert's three matrices in bfloat16 (the
    step reads each whatever the routing), and per expert the ``slots``
    rows it is given (bfloat16 in, the gate's and the up's outputs
    written and read again, float32 out), the ``[slots, held]`` weights
    and the float32 sum."""
    z = sizes(cfg)
    d, f, e = z["d"], z["expert_width"], z["held"]
    weights = e * 3 * d * f * 2
    rows = e * slots * (2 * d + 2 * 2 * 2 * f + 4 * d)
    combine = slots * e * 4 + slots * d * 4
    return z["layers"] * (weights + rows + combine)
