"""The ``phi4flash`` family's numbers, read from a configuration file:
the sizes as run (shared by the adapter and the plain reference, which
share nothing else), and what a decode step's state-space layers and its
attention have to move, in bytes, which ``ssm_state_roofline`` and
``sambay_attn_roofline`` set against their device time.
"""

from __future__ import annotations

#: of the compute dtype the configuration states (bfloat16)
BYTES = 2
#: of the recurrent state (float32: the file's ``assumed``)
STATE_BYTES = 4


def sizes(cfg: dict) -> dict:
    """The sizes the program and the reference both run, under short
    names.  The layer map follows from ``layers`` = 2 h alone: even
    layers up to h Mamba, odd layers below h window attention, h + 1 the
    full layer, then GMU (even) and cross attention (odd).

    ``run.py --rehearse`` overlays GPT-2's key names (``n_embd``,
    ``n_head``, ``n_inner``, ``n_layer``) on any configuration: a file
    that carries them is the tiny preset, and every size the overlay
    does not name is set here beside those it does -- eight layers (three
    Mamba, two window, the full one, a GMU and a cross layer), a window
    of 16 so that the preset's slots of 128 run far past it, the
    published ratios of key/value to query heads and of the inner width
    to the hidden."""
    if "n_embd" in cfg:
        d, heads = cfg["n_embd"], 4 * cfg["n_head"]
        return dict(vocab=cfg["vocab_size"], d=d, layers=8, heads=heads,
                    kv_heads=heads // 2, head_dim=d // heads,
                    ffn=cfg["n_inner"], window=16, inner=2 * d, state=4,
                    taps=4, dt_rank=-(-d // 16), eps=cfg["layer_norm_eps"],
                    std=0.02)
    d = cfg["hidden_size"]
    if cfg["mb_per_layer"] != 2:
        raise ValueError("phi4flash alternates Mamba and attention layer "
                         "by layer: mb_per_layer 2")
    ssm = cfg["mamba"]
    return dict(
        vocab=cfg["vocab_size"], d=d, layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        ffn=cfg["intermediate_size"], window=cfg["sliding_window"],
        inner=ssm["expand"] * d, state=ssm["d_state"], taps=ssm["d_conv"],
        dt_rank=-(-d // 16), eps=cfg["layer_norm_eps"],
        std=cfg["initializer_range"])


def layer_kinds(z: dict) -> list:
    """The kind of every layer, in order (``models/phi4flash.py``'s
    names; the reference takes them from here and not from there)."""
    half = z["layers"] // 2
    out = []
    for li in range(z["layers"]):
        if li > half + 1:
            out.append("attn_cross" if li % 2 else "gmu")
        elif li % 2 == 0:
            out.append("mamba")
        else:
            out.append("attn_full" if li == half + 1 else "attn_window")
    return out


def mamba_layer_params(z: dict) -> int:
    """Parameters of one Mamba layer's mixer."""
    d, e, n, r = z["d"], z["inner"], z["state"], z["dt_rank"]
    return (d * 2 * e + z["taps"] * e + e + e * (r + 2 * n) + r * e + e
            + n * e + e + e * d)


def n_params(z: dict) -> int:
    """Every parameter: the mixers by kind (an attention layer's four
    ``lam`` vectors of a head's width and its sub-norm of two), a gated
    FFN and two LayerNorms a layer, the final norm, the tied embedding."""
    d, e, hd = z["d"], z["inner"], z["head_dim"]
    hq, hkv = z["heads"] * hd, z["kv_heads"] * hd
    lam = 4 * hd + 2 * hd
    self_attn = d * (hq + 2 * hkv) + hq + 2 * hkv + hq * d + d + lam
    of = {"mamba": mamba_layer_params(z), "attn_window": self_attn,
          "attn_full": self_attn, "gmu": 2 * d * e,
          "attn_cross": d * hq + hq + hq * d + d + lam}
    kinds = layer_kinds(z)
    return (sum(of[k] for k in kinds) + len(kinds) * (3 * d * z["ffn"] + 4 * d)
            + 2 * d + z["vocab"] * d)


def row_bytes(cfg: dict) -> int:
    """Bytes of one layer's row as the configuration's widths give them:
    key/value heads x head width x 2 parts (K and V) x 2 bytes."""
    z = sizes(cfg)
    return z["kv_heads"] * z["head_dim"] * 2 * BYTES


def decode_state_bytes(cfg: dict, slots_live: float) -> float:
    """Bytes ONE decode step's Mamba layers have to move: the state
    (float32, ``state x inner`` a slot and layer) and the convolution
    tail of every LIVE slot read and written once, and the layers'
    weights (bfloat16) read once.  The states of dead slots are not work
    the step has to do, whatever reads them."""
    z = sizes(cfg)
    n_mamba = layer_kinds(z).count("mamba")
    slot = (STATE_BYTES * z["state"] * z["inner"]
            + BYTES * (z["taps"] - 1) * z["inner"])
    return n_mamba * (2 * slot * slots_live + BYTES * mamba_layer_params(z))


def decode_attn_bytes(cfg: dict, rows: float) -> float:
    """Bytes ONE decode step's attention has to move for ``rows`` rows,
    counted a READING layer and summed over layers and slots: a live
    context's rows of the full slab once for the full layer and once for
    each cross layer, its rows of each ring, and the new rows written.
    The rows a program reads of dead slots or past a context's end are
    not work the step has to do."""
    return rows * row_bytes(cfg)
