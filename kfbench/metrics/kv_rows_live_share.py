"""Of the per-head key and value rows a decode step read, the share that
belonged to a live context: ``kv_rows_live`` over ``kv_rows_read`` of
``kf:serve.decode_read`` (the first the host's count from the step's
contexts, a window layer's capped at its ring; the second stated by the
cache whose step read them; both summed over the layers that keep such
rows), mean over the traced steps, in percent.  The rest the program
reads under a mask: every position of every slot, whatever is live.
One seed's draw of the traffic: compare parent and change on one seed."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.stats["kv_rows_live"] / s.stats["kv_rows_read"]
                       for s in spans.of(facts).named("serve.decode_read")
                       if s.stats.get("kv_rows_read")), 100.0)
