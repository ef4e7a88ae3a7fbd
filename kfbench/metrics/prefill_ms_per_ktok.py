"""Device time of the prefill programs per thousand prompt tokens they
prefilled, over the traced stretch."""

from kfbench.lib import trace


def read(facts, entry):
    t = trace.of(facts)
    if t is None:
        return None
    runs = trace.module_runs(t, r"^jit__prefill_fn")
    lo, hi = facts["trace"]["t0"], facts["trace"]["t1"]
    steps = [s for s in facts["serve"]["steps"] if lo < s[0] <= hi and s[3]]
    if not runs or not steps:
        return None
    # the trace and the step records may differ by a prefill at an edge
    tokens = sum(s[3] for s in steps) * len(runs) / len(steps)
    return 1e3 * sum(runs) / (tokens / 1e3)
