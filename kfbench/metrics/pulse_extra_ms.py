"""What monitoring adds to a sampled step: the pulse program's time on
the device minus the base program's (medians over the traced runs)."""

from kfbench.lib import stats, trace

BASE, PULSE = r"^jit_step3", r"^jit_pulse_outer"


def read(facts, entry):
    t = trace.of(facts)
    if t is None:
        return None
    base, pulse = trace.module_runs(t, BASE), trace.module_runs(t, PULSE)
    if not base or not pulse:
        return None
    return 1e3 * (stats.median(pulse) - stats.median(base))
