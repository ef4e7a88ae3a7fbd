"""The busiest held expert's live tokens over the mean held expert's, in
a decode step (over all layers); mean over the traced steps."""

from kfbench.lib import decode_paths, spans


def read(facts, entry):
    return spans.mean(r["expert_load_max"] / r["expert_load_mean"]
                      for r in decode_paths.routing(facts)
                      if r["expert_load_mean"])
