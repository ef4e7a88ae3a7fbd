"""The ``evabyte`` family's numbers, read from a configuration file: the
sizes as run (shared by the adapter and the plain reference, which share
nothing else) and what a decode step's attention has to move, in bytes,
which ``eva_attn_roofline`` sets against its device time.
"""

from __future__ import annotations

#: of the compute dtype the configuration states (bfloat16)
BYTES = 2


def sizes(cfg: dict) -> dict:
    """The sizes the program and the reference both run, under short
    names.  ``layers`` is the stage held here, ``init_layers`` the depth
    the initialisation is reckoned from.

    ``run.py --rehearse`` overlays GPT-2's key names (``n_embd``,
    ``n_head``, ``n_inner``, ``n_layer``) on any configuration: a file
    that carries them is the tiny preset, and every size the overlay
    does not name is set here beside those it does -- windows of 32 in
    chunks of 4, so that the preset's slots of 128 hold four windows."""
    common = dict(vocab=cfg["vocab_size"], eps=cfg["rms_norm_eps"],
                  theta=float(cfg["rope_theta"]), std=cfg["init_std"],
                  pred_heads=cfg["num_pred_heads"])
    if "n_embd" in cfg:
        heads = 2 * cfg["n_head"]
        return dict(common, d=cfg["n_embd"], layers=cfg["n_layer"],
                    init_layers=cfg["n_layer"], heads=heads,
                    head_dim=cfg["n_embd"] // heads, ffn=cfg["n_inner"],
                    chunk=4, window=32)
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("evabyte groups no heads: as many key/value heads "
                         "as query heads")
    return dict(
        common, d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        init_layers=cfg["num_hidden_layers_published"],
        heads=cfg["num_attention_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        ffn=cfg["intermediate_size"], chunk=cfg["chunk_size"],
        window=cfg["window_size"])


def layer_params(z: dict) -> int:
    """Parameters of one layer: four square projections, the gated FFN's
    three, two norms' offsets, ``mu`` and ``phi`` a head."""
    d = z["d"]
    return (4 * d * z["heads"] * z["head_dim"] + 3 * d * z["ffn"] + 2 * d
            + 2 * z["heads"] * z["head_dim"])


def row_bytes(cfg: dict) -> int:
    """Bytes of one layer's row, exact or chunk, as the configuration's
    widths give them: heads x head width x 2 parts (K and V) x 2 bytes."""
    z = sizes(cfg)
    return z["heads"] * z["head_dim"] * 2 * BYTES


def decode_attn_bytes(cfg: dict, rows: float) -> float:
    """Bytes ONE decode step's attention has to move for ``rows`` rows
    (summed over layers and slots): the exact rows of the live contexts'
    open windows and the chunk rows of their closed ones read once, the
    new rows and the chunk rows of completed chunks written.  The rows a
    program reads of dead slots, of closed windows' exact rows or of
    chunk rows no context may see yet are not work the step has to do."""
    return rows * row_bytes(cfg)
