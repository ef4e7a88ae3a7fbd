"""What a finished request costs the host: mean ``kf:serve.complete``
(the slab slice, its fetch from the device, a copy per page)."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.dur for s in spans.of(facts).named("serve.complete")),
                      1e3)
