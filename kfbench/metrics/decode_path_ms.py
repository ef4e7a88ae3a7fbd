"""``decode_path_ms.<scope>``: device time per ``jit__decode_fn`` run of
the operations whose scope path holds ``<scope>`` (``moe_router``,
``moe_experts``, ``moe_shared`` nest inside ``mlp``, ``attn_window`` and
``attn_full`` inside ``attn_core``: they stand outside the vocabulary of
``scope_ms_per_step``, whose entries go on adding up to the busy time).
The reader walks the path itself."""

from kfbench.lib import decode_paths


def read(facts, entry):
    return decode_paths.scope_ms_per_run(facts,
                                         entry["name"].split(".", 1)[1])
