"""``scope_ms_per_step.<scope>``: device time per step of the first
device's operations under each ``jax.named_scope`` of the program.  A
train cell sums over its traced steps; the chat cell's ``decode_*`` and
``kv_write`` over the operations inside ``jit__decode_fn`` runs, per
run.  A fusion counts under the scope its event carries; what carries
none of the vocabulary is the ``*_unscoped`` entry.  Each instant goes
to the innermost operation open then (a ``while`` holds its body's), so
a cell's entries add up to the device's busy time per step."""

from kfbench.lib import spans, trace

DECODE = r"^jit__decode_fn"
#: the chat cell's entries, as sets of scopes (None: no scope)
CHAT = {
    "kv_write": {"kv_write"},
    "decode_attn_core": {"attn_core"},
    "decode_dense": {"attn_proj", "mlp", "head", "norm", "embed"},
}


def read(facts, entry):
    sp = spans.of(facts)
    which = entry["name"].split(".", 1)[1]
    if which in CHAT or which == "decode_unscoped":
        steps, by = spans.runs(sp, DECODE), spans.scope_seconds(sp, DECODE)
        named = set().union(*CHAT.values())
        take = CHAT.get(which) or {s for s in by if s not in named}
    else:
        steps, by = spans.runs(sp, trace.TRAIN_STEP), spans.scope_seconds(sp)
        take = {None} if which == "train_unscoped" else {which}
    # a trace whose operations carry no scope at all has nothing to read
    if steps < spans.MIN_SAMPLES or not any(s for s in by):
        return None
    return 1e3 * sum(by.get(s, 0.0) for s in take) / steps
