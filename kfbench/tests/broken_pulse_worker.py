"""The worker, over a training step whose pulse program (every tenth
call) loses its update: the base program's steps are sound."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import kungfu_tpu.parallel.train as program  # noqa: E402

from kfbench import worker  # noqa: E402

sound = program.dp_train_step


def broken(*args, **kwargs):
    # (nothing donated: the state handed in can be handed back)
    step = sound(*args, **dict(kwargs, donate=False))

    def pulse_unchanged(params, opt_state, batch):
        sampled = step.pulse.samples
        new_params, new_state, loss = step(params, opt_state, batch)
        if step.pulse.samples != sampled:
            return params, opt_state, loss
        return new_params, new_state, loss

    pulse_unchanged.pulse = step.pulse
    return pulse_unchanged


program.dp_train_step = broken
sys.exit(worker.main())
