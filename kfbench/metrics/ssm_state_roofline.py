"""The Mamba layers' share of their roofline in a decode step: the least
time the chip could take for what the step HAS to move --
``lib/phi4flash.py::decode_state_bytes`` over its memory bandwidth: the
recurrent state and the convolution tail of every LIVE slot (the traced
steps' mean ``state_slots_live``) read and written once, and the layers'
weights read once -- against the device time under ``ssm_state`` and
``ssm_proj`` together (bound by bytes: a number of the state takes some
six operations for its eight bytes).  Counted so, a program that moves
every slot's state reads low for it, passes over the state beyond one
read and one write read low too, and a kernel that stops at the live
slots does not make the count stale.  A program without the scopes (any
before this family's) gives nothing."""

from kfbench.lib import decode_paths, phi4flash, spans


def read(facts, entry):
    took_ms = [decode_paths.scope_ms_per_run(facts, scope)
               for scope in ("ssm_state", "ssm_proj")]
    if not all(took_ms) or "peaks" not in facts:
        return None
    live = spans.mean(float(s.stats["state_slots_live"])
                      for s in spans.of(facts).named("serve.decode_read")
                      if "state_slots_live" in s.stats)
    if live is None:
        return None
    least_s = phi4flash.decode_state_bytes(
        facts["spec"]["config"], live) / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(took_ms) / 1e3)
