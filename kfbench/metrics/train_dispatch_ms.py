"""The un-waited call of the step program, timed inside the wrapper:
median ``kf:step.dispatch`` (``step_dispatch_ms`` is the same call timed
from outside, wrapper and all)."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.median(
        (s.dur for s in spans.of(facts).named("step.dispatch")), 1e3)
