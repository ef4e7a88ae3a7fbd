"""Of the latent rows a decode step read, the share that belonged to a
live context: ``latent_rows_live`` over ``latent_rows_read`` of
``kf:serve.decode_read`` (the first counted by the step itself, the
second stated by the cache that read it; both a layer), mean over the
traced steps, in percent.  The rest the program reads
under a mask: every position of every slot, whatever is live."""

from kfbench.lib import spans


def read(facts, entry):
    return spans.mean((s.stats["latent_rows_live"]
                       / s.stats["latent_rows_read"]
                       for s in spans.of(facts).named("serve.decode_read")
                       if s.stats.get("latent_rows_read")), 100.0)
