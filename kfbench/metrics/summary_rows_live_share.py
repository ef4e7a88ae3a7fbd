"""Of the rows that stand for several positions a decode step read (the
chunk rows of ``serve/pooled.py``), the share a live context may see:
``summary_rows_live`` over ``summary_rows_read`` of
``kf:serve.decode_read`` (the first the host's count from the step's
contexts, the chunk rows of each context's CLOSED windows; the second
stated by the cache whose step read them; both summed over the layers),
mean over the traced steps, in percent.  The rest the program reads under
a mask: every chunk row of every slot, those of dead slots, of the open
window and of positions no request has reached among them.  One seed's
draw of the traffic: compare parent and change on one seed."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.stats["summary_rows_live"]
                       / s.stats["summary_rows_read"]
                       for s in spans.of(facts).named("serve.decode_read")
                       if s.stats.get("summary_rows_read")), 100.0)
