"""The worker, over a decode step whose tokens are altered where they
are produced."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402

from kfbench import worker  # noqa: E402

sound = InferenceEngine._decode_fn


def broken(self, *args):
    k, v, tokens = sound(self, *args)
    return k, v, (tokens + 1) % self.model.cfg.vocab_size


InferenceEngine._decode_fn = broken
sys.exit(worker.main())
