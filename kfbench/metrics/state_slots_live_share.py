"""Of the slots whose recurrent state a decode step read and wrote back,
the share the step was for: ``state_slots_live`` over
``state_slots_read`` of ``kf:serve.decode_read`` (the first counted by
the step itself, the second stated by the cache that moved them), mean
over the traced steps, in percent.  The rest the program moves to leave
as it was: every slot's state, whatever is live."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.stats["state_slots_live"]
                       / s.stats["state_slots_read"]
                       for s in spans.of(facts).named("serve.decode_read")
                       if s.stats.get("state_slots_read")), 100.0)
