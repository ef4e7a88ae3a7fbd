"""Tokens trained per second per chip in the user's loop: every token of
every block of the window over the window's measured time, stalls and
all."""


def read(facts, entry):
    t = facts["train"]
    tokens = t["tokens_per_step"] * t["block_steps"] * len(t["block_s"])
    return tokens / sum(t["block_s"]) / t["chips"]
