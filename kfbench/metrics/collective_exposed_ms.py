"""Per step, the collective-operation time during which no other
operation runs on that device: what S-SGD's all-reduces cost the step
because nothing hides them."""

from kfbench.lib import trace


def read(facts, entry):
    t = trace.of(facts)
    if t is None:
        return None
    steps = len(trace.module_runs(t, trace.TRAIN_STEP))
    if not steps or not trace.op_count(t, trace.COLLECTIVE.pattern):
        return None
    return 1e3 * trace.exposed_collective_seconds(t) / steps
