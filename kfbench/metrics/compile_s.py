"""Seconds the step factory's two whole-step programs cost set-up: each
program's first call (trace, lower, backend compile or cache read, one
run) less the steady time of the step it ran."""

from kfbench.lib import stats


def read(facts, entry):
    parts, t = facts["setup_parts"], facts["train"]
    steady = stats.median(t["block_s"]) / t["block_steps"]
    return parts["first_step"] + parts["first_pulse_step"] - 2 * steady
