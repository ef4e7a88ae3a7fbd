"""The share of the decode program's slots that held a live request:
mean ``batch`` over ``width`` of ``kf:serve.decode``, in percent (the
program computes every slot, live or not)."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.stats["batch"] / s.stats["width"]
                       for s in spans.of(facts).named("serve.decode")
                       if s.stats.get("width")), 100.0)
