"""Tokens per block of steps over the median block time, per chip: the
rate of the window's undisturbed stretches.  Where the end-to-end rate
falls short of it, single slow blocks cost the difference
(``block_stall_ms`` says how much time)."""

from kfbench.lib import stats


def read(facts, entry):
    t = facts["train"]
    per_block = t["tokens_per_step"] * t["block_steps"] / t["chips"]
    return per_block / stats.median(t["block_s"])
