"""Host clock around the un-waited call of the step: what the host pays
to hand one step to the device (median over the window's steps)."""

from kfbench.lib import stats


def read(facts, entry):
    return 1e3 * stats.median(facts["train"]["dispatch_s"])
