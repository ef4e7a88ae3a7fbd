"""What a decode step's attention over per-head key and value rows has
to move, and the device time it is held against: the two sides of
``kv_attn_roofline``.

The program's side is four attrs of every ``kf:serve.decode_read`` span
(``serve/caches.py::kv_rows``; docs/tracing.md): ``kv_rows_live``, the
rows the step's live contexts HAD to read, summed over the layers that
keep such rows (a window layer at most its ring), ``kv_rows_read``, the
rows it did read, ``kv_rows_written`` and ``kv_row_bytes``, one layer's
row in K and V.  The least time is counted over the LIVE rows and the
rows written, not over the slabs: a program that reads every row of
every slot reads low for it, and a kernel that stops reading dead rows
does not make the count stale.  The row's bytes are the program's word,
so they are held to the configuration's own widths here: a program
cannot state itself a narrower row than its model has.

The time is everything the decode program spends on those rows: the
operations whose scope path holds ``attn_window`` or ``attn_full`` or,
in a program that has neither (the dense block), ``attn_core``, and
those under ``kv_write``.  The write is inside because a kernel that
walks a slot's live rows writes the new one on its way: whichever scope
that time lands under, the sum is the same work.

A trace of a program that states no ``kv_*`` attr (any before the PR
that added them, and the latent cache, whose rows are no K/V rows)
gives nothing, and the readers return None.
"""

from __future__ import annotations

from kfbench.lib import cohere2, decode_paths, solar_open2, spans

ATTRS = ("kv_rows_live", "kv_rows_read", "kv_rows_written", "kv_row_bytes")
#: of the compute dtype every configuration here states (bfloat16)
BYTES = 2
#: family -> the sizes of a configuration that groups its heads
_SIZES = {"cohere2_moe": cohere2.sizes, "solar_open2": solar_open2.sizes}


def config_row_bytes(cfg: dict):
    """Bytes of one layer's row as the configuration's widths give them:
    key/value heads x head width x 2 parts (K and V) x 2 bytes; None for
    a family that keeps no such rows."""
    if cfg["family"] == "gpt2":
        heads, width = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    elif cfg["family"] in _SIZES:
        z = _SIZES[cfg["family"]](cfg)
        heads, width = z["kv_heads"], z["head_dim"]
    else:
        return None
    return heads * width * 2 * BYTES


def steps(facts: dict) -> list:
    """The ``kv_*`` attrs of every traced decode step that carries them,
    ``[{attr: number}]``."""
    return [{k: float(s.stats[k]) for k in ATTRS}
            for s in spans.of(facts).named("serve.decode_read")
            if all(k in s.stats for k in ATTRS)]


def least_seconds(facts: dict):
    """The least seconds a traced step's K/V traffic can take: the mean
    step's live rows and written rows, at the row's bytes, over the
    chip's memory bandwidth."""
    said = steps(facts)
    if len(said) < spans.MIN_SAMPLES or "peaks" not in facts:
        return None
    row = said[0]["kv_row_bytes"]
    want = config_row_bytes(facts["spec"]["config"])
    if want is None:
        return None
    if row < want or any(s["kv_row_bytes"] != row for s in said):
        raise ValueError(
            f"kfbench: kf:serve.decode_read states kv_row_bytes {row}, "
            f"and the configuration's key/value heads and head width make "
            f"a row {want} bytes: a narrower row, or one that changes "
            "from step to step, is not this model's")
    rows = sum(s["kv_rows_live"] + s["kv_rows_written"] for s in said
               ) / len(said)
    return rows * row / facts["peaks"]["hbm_bytes_per_s"]


def attn_ms_per_run(facts: dict):
    """Device milliseconds per decode run of the attention over the K/V
    rows and of their write (the module's docstring says which scopes)."""
    runs, by_path = decode_paths.decode_path_seconds(facts)
    if runs < spans.MIN_SAMPLES or not any(by_path):
        return None
    parts = {p: set(p.split("/")) for p in by_path}
    named = {"attn_window", "attn_full"}
    if not any(named & have for have in parts.values()):
        named = {"attn_core"}
    named.add("kv_write")
    took = sum(s for p, s in by_path.items() if named & parts[p])
    return 1e3 * took / runs if took else None
