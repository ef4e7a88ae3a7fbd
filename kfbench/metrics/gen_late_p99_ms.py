"""How late the load generator ran: submit time minus due time, 99th
percentile.  A starved generator must not read as a fast server."""

from kfbench.lib import stats


def read(facts, entry):
    late = facts["serve"]["gen_late_s"]
    return 1e3 * stats.percentile(late, 99) if late else None
