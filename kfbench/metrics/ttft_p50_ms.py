"""Time from when a request was due to its first token: the median over
the requests due in the window."""

from kfbench.lib import records, stats


def read(facts, entry):
    return 1e3 * stats.percentile(records.ttfts(facts), 50)
