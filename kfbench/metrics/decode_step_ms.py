"""The decode program's time on the device per step (median over the
traced runs)."""

from kfbench.lib import stats, trace


def read(facts, entry):
    t = trace.of(facts)
    if t is None:
        return None
    runs = trace.module_runs(t, r"^jit__decode_fn")
    return 1e3 * stats.median(runs) if runs else None
