"""The host waiting for the decode step's tokens: median
``kf:serve.decode_read``."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.median(
        (s.dur for s in spans.of(facts).named("serve.decode_read")), 1e3)
