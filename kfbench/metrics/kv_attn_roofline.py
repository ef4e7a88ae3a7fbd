"""The share of its roofline at which a decode step attends over its
per-head key and value rows: the least time the chip could take for the
rows the step HAS to move (``lib/kv.py::least_seconds``: the live
contexts' rows read once and the new rows written, at the row's bytes,
over the memory bandwidth; the traced steps' mean counts) against the
device time of the attention over the slabs and of the row write
(``lib/kv.py::attn_ms_per_run``).  Bound by memory: a decode step does
two operations a byte of K and V.  Counted over live rows, so a program
that reads every row of every slot reads low, and a kernel that stops
does not make the count stale."""

from kfbench.lib import kv


def read(facts, entry):
    took_ms = kv.attn_ms_per_run(facts)
    least_s = kv.least_seconds(facts) if took_ms else None
    if least_s is None:
        return None
    return 100.0 * least_s / (took_ms / 1e3)
