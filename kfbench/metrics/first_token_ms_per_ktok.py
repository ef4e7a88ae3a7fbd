"""The prefill as the engine sees it, per thousand prompt tokens: over
the requests due in the window that finished, the engine's own time from
a request's admission to its first token (``engine_ttft_s`` less
``queue_s`` of its ``done`` event: ``first_token_s - admitted_s``, the
restore of cached pages, the prefill and the wait for the decode step in
flight ahead of it), summed, over their prompts' tokens, summed.  Every
request of the window is a sample, where the device-trace reader of the
prefill wants an admission inside the traced 4 s."""

from kfbench.lib import records


def read(facts, entry):
    took = tokens = 0.0
    for r in records.counted(facts):
        if r["engine_ttft_s"] is not None and r["queue_s"] is not None:
            took += r["engine_ttft_s"] - r["queue_s"]
            tokens += r["prompt_len"]
    return 1e6 * took / tokens if tokens else None
