"""Time of the window beyond what its blocks would have taken at the
median block's pace: what slow stretches cost the end-to-end rate."""

from kfbench.lib import stats


def read(facts, entry):
    b = facts["train"]["block_s"]
    return 1e3 * (sum(b) - len(b) * stats.median(b))
