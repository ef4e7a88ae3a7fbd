"""Model FLOP/s utilisation: the operations forward and backward
require per token (causal attention counted as its lower triangle, no
recomputation), times tokens per second per chip, over the chip's peak."""

from kfbench.lib import files, flops


def read(facts, entry):
    if "peaks" not in facts:  # the CPU rehearsal: no peak to divide by
        return None
    spec = facts["spec"]
    rate = files.load_module("metrics", "train_tokens_per_s_chip").read(
        facts, entry)
    per_token = flops.train_flops_per_token(
        spec["config"], spec["traffic"]["seq_len"])
    return 100.0 * per_token * rate / facts["peaks"]["bf16_flops"]
