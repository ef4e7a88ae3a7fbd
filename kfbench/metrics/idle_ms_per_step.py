"""``idle_ms_per_step.<x>``: the first device's idle time in the traced
stretch while the innermost ``kf:`` span open on the host was one of
``<x>``'s, over the stretch's steps.  A cell's entries add up to its
idle time per step."""

from kfbench.lib import spans

#: the step span of each family, and which spans each entry takes;
#: ``*_other`` takes the step span itself, every span not named here
#: and what no span covers
SERVE = ("serve.step", {
    "complete": ("serve.complete",),
    "admit": ("serve.admit", "serve.prefill", "serve.prefill_read"),
    "decode": ("serve.decode", "serve.decode_read"),
    "serve_other": None,
})
TRAIN = ("step.train", {
    "pulse_sync": ("pulse.sync",),
    "dispatch": ("step.dispatch",),
    "train_other": None,
})


def read(facts, entry):
    sp = spans.of(facts)
    which = entry["name"].split(".", 1)[1]
    step, parts = SERVE if which in SERVE[1] else TRAIN
    steps = len(sp.named(step))
    idle = spans.idle_by_span(sp, step)
    if steps < spans.MIN_SAMPLES or not idle:
        return None
    if parts[which] is None:
        named = {spans.PREFIX + n for ns in parts.values() if ns for n in ns}
        seconds = sum(v for k, v in idle.items() if k not in named)
    else:
        seconds = sum(idle.get(spans.PREFIX + n, 0.0) for n in parts[which])
    return 1e3 * seconds / steps
