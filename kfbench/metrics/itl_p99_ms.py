"""99th percentile of the gap between successive tokens of one request,
over every gap in the window."""

from kfbench.lib import records, stats


def read(facts, entry):
    return 1e3 * stats.percentile(records.itl_gaps(facts), 99)
