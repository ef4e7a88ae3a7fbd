"""How far the decode program's runs differ from each other inside ONE
traced stretch: the 90th less the 10th percentile of the
``jit__decode_fn`` runs' device times over their median, in percent.  A
step whose operations' shapes and trip counts are fixed reads a few
hundredths; one whose time follows the data (the live contexts, the
routing) shows here before it shows in six seeds of ``itl_p50_ms``.  It
cannot tell two shapes under one program name from noise: a reading
over 1 % says look at the runs, not why."""

from kfbench.lib import spans, stats, trace


def read(facts, entry):
    t = trace.of(facts)
    if t is None:
        return None
    runs = trace.module_runs(t, r"^jit__decode_fn")
    if len(runs) < spans.MIN_SAMPLES:
        return None
    return 100.0 * (stats.percentile(runs, 90) - stats.percentile(runs, 10)
                    ) / stats.median(runs)
