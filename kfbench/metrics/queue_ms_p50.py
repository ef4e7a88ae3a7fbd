"""The engine's own ``queue_s`` (submit to admission) of the requests
due in the window, median."""

from kfbench.lib import records, stats


def read(facts, entry):
    q = [r["queue_s"] for r in records.counted(facts)
         if r["queue_s"] is not None]
    return 1e3 * stats.percentile(q, 50) if q else None
