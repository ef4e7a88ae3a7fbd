"""What one ``InferenceEngine.step`` costs the host: a ``kf:serve.step``
span's duration less the two waits for the device inside it
(``prefill_read``, ``decode_read``), median over the traced steps."""

from kfbench.lib import spans


def read(facts, entry):
    sp = spans.of(facts)
    return spans.median((sp.self_seconds(s, "serve.prefill_read",
                                         "serve.decode_read")
                         for s in sp.named("serve.step")), 1e3)
