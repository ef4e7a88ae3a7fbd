"""What an admission costs the host besides its prefill: mean self time
of ``kf:serve.admit`` (less ``prefill``, the dispatch, and
``prefill_read``, the wait)."""

from kfbench.lib import spans


def read(facts, entry):
    sp = spans.of(facts)
    return spans.mean((sp.self_seconds(s, "serve.prefill", "serve.prefill_read")
                       for s in sp.named("serve.admit")), 1e3)
