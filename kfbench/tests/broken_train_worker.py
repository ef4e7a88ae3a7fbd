"""The worker, over a training step that returns its state unchanged."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import kungfu_tpu.parallel.train as program  # noqa: E402

from kfbench import worker  # noqa: E402

sound = program.dp_train_step


def broken(*args, **kwargs):
    # (nothing donated: the state handed in is the state handed back)
    step = sound(*args, **dict(kwargs, donate=False))

    def unchanged(params, opt_state, batch):
        _, _, loss = step(params, opt_state, batch)
        return params, opt_state, loss

    unchanged.pulse = step.pulse
    return unchanged


program.dp_train_step = broken
sys.exit(worker.main())
