"""The pulse step's host sync, ``float(gl), float(gg)``: the host waits
there until the device has finished every step handed to it.  Median
``kf:pulse.sync``."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.median((s.dur for s in spans.of(facts).named("pulse.sync")),
                        1e3)
