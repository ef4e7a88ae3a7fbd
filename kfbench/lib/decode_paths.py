"""What an expert model's per-layer metrics read from a traced run: the
decode program's operations by the WHOLE scope path they were traced
under (``lib/spans.py`` keeps only the innermost name of its own
vocabulary, and ``moe_experts`` or ``attn_window`` nest inside ``mlp``
and ``attn_core``), and the routing attrs of the
``kf:serve.decode_read`` spans (docs/tracing.md).

A trace of a program that writes neither -- any program before the PR
that added them -- gives nothing, and every reader then returns None.
"""

from __future__ import annotations

import re

from kfbench.lib import spans, trace

DECODE = r"^jit__decode_fn"
#: the routing attrs of a ``kf:serve.decode_read`` span
ROUTING = ("experts_touched", "experts_held", "expert_load_max",
           "expert_load_mean")


def path_seconds(ops, runs):
    """{scope path: seconds} of ``ops`` ``[(path, start, end)]`` inside
    the intervals ``runs``, each instant going to the innermost operation
    open then (a ``while`` holds its body's)."""
    inside = trace.union(runs)
    return {p: trace.total(spans.both(iv, inside))
            for p, iv in spans.innermost(ops).items()}


def device_ops(xplane: str):
    """([(scope path, start, end)] of the first device's operations,
    [(start, end)] of its decode program's runs), in seconds."""
    from jax.profiler import ProfileData

    paths = spans.scope_paths(xplane)
    ops, runs = [], []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops = [(paths.get(e.name, ""), e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
            elif line.name == trace.MODULES_LINE:
                runs = [(e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events if re.search(DECODE, e.name)]
        break  # the first device
    return ops, runs


def decode_path_seconds(facts: dict):
    """(runs of the decode program in the traced stretch, {scope path:
    seconds} of the first device's operations inside them)."""
    tr = facts.get("trace")
    if not tr:
        return 0, {}
    if "decode_paths" not in tr:
        ops, runs = device_ops(trace.find_xplane(tr["dir"]))
        tr["decode_paths"] = (len(runs), path_seconds(ops, runs))
    return tr["decode_paths"]


def scope_ms_per_run(facts: dict, name: str):
    """Device milliseconds per decode run of the operations whose scope
    path holds ``name`` as one of its parts."""
    runs, by_path = decode_path_seconds(facts)
    if runs < spans.MIN_SAMPLES or not any(by_path):
        return None
    return 1e3 * sum(s for p, s in by_path.items()
                     if name in p.split("/")) / runs


def routing(facts: dict):
    """The routing attrs of every traced decode step that carries them,
    ``[{attr: number}]``: the host reads a step's routing with its
    tokens, under ``kf:serve.decode_read``."""
    return [{k: float(s.stats[k]) for k in ROUTING}
            for s in spans.of(facts).named("serve.decode_read")
            if all(k in s.stats for k in ROUTING)]
