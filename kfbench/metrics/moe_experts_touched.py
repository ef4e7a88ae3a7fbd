"""The share of the held experts (over all layers) that received a live
token in a decode step, mean over the traced steps, in percent.  The
decode program reads every held expert whatever this says; it is the
measure of how far the cut's batch is from a deployment's, where every
expert is touched every step."""

from kfbench.lib import decode_paths, spans


def read(facts, entry):
    return spans.mean((r["experts_touched"] / r["experts_held"]
                       for r in decode_paths.routing(facts)
                       if r["experts_held"]), 100.0)
