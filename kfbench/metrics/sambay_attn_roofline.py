"""The share of its roofline at which a decode step attends over rings
and the one full slab: the least time the chip could take for the rows
the step HAS to move -- ``lib/phi4flash.py::decode_attn_bytes`` over its
memory bandwidth: ``kv_rows_live`` (counted A READING LAYER: a live
context's rows of the slab once for the full layer and once for each
cross layer, and of each ring) read once and ``kv_rows_written``
written, the traced steps' mean counts, at the row's bytes -- against
the device time under ``attn_window``, ``attn_full``, ``attn_cross`` and
``kv_write`` together (bound by memory: under two operations a byte of K
and V at four query rows a pair).  The row's bytes are the program's
word (``kv_row_bytes``), so they are held to the configuration's own
widths, as ``lib/kv.py`` holds them.  Counted over LIVE rows, so a
program that reads every row of every slot reads low, and a kernel that
stops does not make the count stale.  A program without ``attn_cross``
(any before this family's) gives nothing."""

from kfbench.lib import decode_paths, phi4flash, spans

ATTRS = ("kv_rows_live", "kv_rows_written", "kv_row_bytes")
SCOPES = {"attn_window", "attn_full", "attn_cross", "kv_write"}


def read(facts, entry):
    runs, by_path = decode_paths.decode_path_seconds(facts)
    if runs < spans.MIN_SAMPLES or "peaks" not in facts:
        return None
    took = sum(s for p, s in by_path.items() if SCOPES & set(p.split("/")))
    if not took or not any("attn_cross" in p.split("/") for p in by_path):
        return None
    said = [s.stats for s in spans.of(facts).named("serve.decode_read")
            if all(k in s.stats for k in ATTRS)]
    if len(said) < spans.MIN_SAMPLES:
        return None
    cfg = facts["spec"]["config"]
    want = phi4flash.row_bytes(cfg)
    if any(s["kv_row_bytes"] != want for s in said):
        raise ValueError(
            f"kfbench: kf:serve.decode_read states kv_row_bytes "
            f"{said[0]['kv_row_bytes']}, and the configuration's heads and "
            f"head width make a row {want} bytes")
    rows = sum(float(s["kv_rows_live"] + s["kv_rows_written"])
               for s in said) / len(said)
    least_s = phi4flash.decode_attn_bytes(cfg, rows) \
        / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (took / runs)
