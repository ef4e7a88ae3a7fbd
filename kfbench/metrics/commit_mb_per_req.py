"""Megabytes a finished request's page commit fetches from the device:
mean ``bytes`` of ``kf:serve.complete``."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.stats["bytes"]
                       for s in spans.of(facts).named("serve.complete")
                       if "bytes" in s.stats), 1e-6)
