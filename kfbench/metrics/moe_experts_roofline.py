"""The routed experts' share of their roofline in a decode step: the
bytes the product has to move (``lib/cohere2.py::decode_expert_bytes``:
every held expert's weights, which the step reads whatever the routing,
and the rows in and out) over the chip's memory bandwidth, against the
device time under ``moe_experts``.  Bound by memory at these shapes: 32
rows against 100 MB of weights an expert."""

from kfbench.lib import cohere2, decode_paths


def read(facts, entry):
    took_ms = decode_paths.scope_ms_per_run(facts, "moe_experts")
    if not took_ms or "peaks" not in facts:
        return None
    spec = facts["spec"]
    byts = cohere2.decode_expert_bytes(
        spec["config"], spec["traffic"]["engine"]["max_batch"])
    least_s = byts / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (took_ms / 1e3)
