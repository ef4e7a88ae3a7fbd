"""Median gap between successive tokens of one request, over every gap
in the window: a decode step with no admission in it."""

from kfbench.lib import records, stats


def read(facts, entry):
    return 1e3 * stats.percentile(records.itl_gaps(facts), 50)
