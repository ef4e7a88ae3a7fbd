"""The flash kernels' share of their roofline: the least time the chip
could take for the calls traced (operations and bytes from their shapes,
``lib/flops.py``; causal, so the lower triangle) over the time they
took.  All three are bound by compute at these shapes (the reader
checks, and says otherwise on stderr)."""

import sys

from kfbench.lib import flops, trace


def read(facts, entry):
    t = trace.of(facts)
    if t is None or "peaks" not in facts:
        return None
    spec = facts["spec"]
    cfg, tr = spec["config"], spec["traffic"]
    least = took = 0.0
    for kernel in flops.FLASH_PRODUCTS:
        # in the operation's own name (a label starts with it), followed
        # by "_" or the end, so that dq never matches dkv
        pattern = r"^\S*" + kernel + r"(?![a-z])"
        ops, byts = flops.flash_call(
            kernel, tr["global_batch"] // spec["cell"]["chips"]
            * cfg["n_head"], tr["seq_len"],
            cfg["n_embd"] // cfg["n_head"])
        seconds, bound = flops.roofline_seconds(ops, byts, facts["peaks"])
        if bound != "compute":
            print(f"flash_roofline: {kernel} is bound by {bound}",
                  file=sys.stderr)
        least += seconds * trace.op_count(t, pattern)
        took += trace.op_seconds(t, pattern)
    return 100.0 * least / took if took else None
