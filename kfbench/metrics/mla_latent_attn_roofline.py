"""The latent attention's share of its roofline in a decode step: the
least time the chip could take for the work the step HAS to do -- the
larger of ``lib/pangu.py::decode_latent_flops`` over the chip's bf16 peak
and ``decode_latent_bytes`` over its memory bandwidth, both over the rows
of LIVE contexts (the traced steps' mean ``latent_rows_live``), not over
the slab -- against the device time under ``mla_latent_attn``.  Counted
so, a program that reads every row of every slot reads low for it, and
one that stops reading dead rows does not make the count stale."""

from kfbench.lib import decode_paths, pangu, spans


def read(facts, entry):
    took_ms = decode_paths.scope_ms_per_run(facts, "mla_latent_attn")
    if not took_ms or "peaks" not in facts:
        return None
    live = spans.mean(float(s.stats["latent_rows_live"])
                      for s in spans.of(facts).named("serve.decode_read")
                      if "latent_rows_live" in s.stats)
    if live is None:
        return None
    spec, peaks = facts["spec"], facts["peaks"]
    cfg = spec["config"]
    slots = spec["traffic"]["engine"]["max_batch"]
    least_s = max(
        pangu.decode_latent_flops(cfg, live) / peaks["bf16_flops"],
        pangu.decode_latent_bytes(cfg, live, slots)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms / 1e3)
