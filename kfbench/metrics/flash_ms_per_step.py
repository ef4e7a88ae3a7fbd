"""Device time of the three flash-attention kernels per training step:
the summed durations of the trace's events named ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` over the steps traced."""

from kfbench.lib import trace


def read(facts, entry):
    t = trace.of(facts)
    if t is None:
        return None
    steps = len(trace.module_runs(t, trace.TRAIN_STEP))
    seconds = trace.op_seconds(t, r"^\S*flash_(fwd|bwd_dq|bwd_dkv)")
    return 1e3 * seconds / steps if steps and seconds else None
