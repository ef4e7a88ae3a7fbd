"""Time from when a request was due to its first token: the 90th
percentile over the requests due in the window (some fifty: a tail too
thin to judge a PR by, recorded beside the median)."""

from kfbench.lib import records, stats


def read(facts, entry):
    return 1e3 * stats.percentile(records.ttfts(facts), 90)
