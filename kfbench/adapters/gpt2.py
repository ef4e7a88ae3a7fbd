"""GPT-2 family: what joins a configuration file to the program.

``program_model`` builds the program's own model from the published
sizes; ``init_params`` makes the weights, by the published
initialisation, in the tree the program's ``Transformer`` reads -- the
benchmark makes them, the program and the reference are both handed
them.  Meant to run under one ``jax.jit`` so that every leaf is made on
the device in one call.
"""

from __future__ import annotations

import math


def program_model(cfg: dict):
    from kungfu_tpu.models.transformer import Transformer, TransformerConfig

    return Transformer(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_layers=cfg["n_layer"], n_heads=cfg["n_head"], d_ff=cfg["n_inner"],
        max_seq=cfg["n_positions"], dropout=0.0, causal=True, pos="learned",
        dtype="bfloat16"))


def init_params(cfg: dict, key):
    import jax
    import jax.numpy as jnp

    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * cfg["n_layer"])
    keys = iter(jax.random.split(key, 3 + 6 * cfg["n_layer"]))

    def w(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def dense(n_in, n_out, s=std):
        return {"w": w((n_in, n_out), s), "b": jnp.zeros((n_out,), jnp.float32)}

    def ln():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    params = {"embed": {"table": w((v, d))},
              "pos_embed": {"table": w((cfg["n_positions"], d))}}
    for i in range(cfg["n_layer"]):
        params[f"layer_{i}"] = {
            "ln1": ln(), "wq": dense(d, d), "wk": dense(d, d),
            "wv": dense(d, d), "wo": dense(d, d, out_std), "ln2": ln(),
            "ffn_in": dense(d, f), "ffn_out": dense(f, d, out_std)}
    params["ln_f"] = ln()
    params["head"] = {"w": w((d, v))}
    return params


def check_tree(cfg: dict, params) -> None:
    """The weights have the shapes the program's own ``init`` gives."""
    import jax

    want = jax.eval_shape(program_model(cfg).init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), want)
    if got != want:
        raise RuntimeError("kfbench's GPT-2 weights no longer have the "
                           "program's tree: adapters/gpt2.py is stale")


def n_params(cfg: dict) -> int:
    d, f, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    layer = 4 * (d * d + d) + (d * f + f) + (f * d + d) + 4 * d
    return v * d + cfg["n_positions"] * d + cfg["n_layer"] * layer + 2 * d + d * v
