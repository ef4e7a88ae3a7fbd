#!/usr/bin/env python3
"""Compile a train cell's whole step for a described ``v5e:2x2`` -- no
chip needed -- and print what the compiler says it needs on each device.

This is how a train traffic file's ``global_batch`` is chosen: per chip
the largest even number of sequences whose compiled step (arguments,
outputs and temporaries of ``memory_analysis()``) leaves a tenth of the
chip's memory free.  Nothing runs, so it says nothing of times or results.

    JAX_PLATFORMS=cpu python kfbench/tools/compile_for_chip.py \
        --config gpt2-medium --traffic train-packed-1k --chips 1 --batch 4 6 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.dirname(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--batch", type=int, nargs="+", required=True,
                    help="per-chip batches to compile, in order")
    ap.add_argument("--pulse", action="store_true",
                    help="also compile the pulse step (every tenth call)")
    a = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kfbench.lib import files, peaks
    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel.train import dp_train_step

    # the routers (attention, xent, head) ask the backend which branch
    # to take; this script compiles for the chip, so it answers for them
    jax.default_backend = lambda: "tpu"
    cfg = files.load_config(a.config)
    traffic = files.load_traffic(a.traffic)
    adapter = files.load_adapter(cfg["family"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = list(topo.devices[:a.chips])
    comm = Communicator(devices=devs, local_size=len(devs))
    model = adapter.program_model(cfg)
    opt = traffic["optimizer"]
    tx = synchronous_sgd(optax.adamw(
        opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"]), comm.axis)
    rep = comm.replicated_sharding()
    shard = NamedSharding(comm.mesh, P(comm.axis))

    def shaped(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = jax.eval_shape(
        lambda k: adapter.init_params(cfg, k), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(tx.init, params)
    params, opt_state = shaped(params, rep), shaped(opt_state, rep)
    step = dp_train_step(model.loss, tx, comm, donate=traffic["donate"])
    hbm = peaks.PEAKS["TPU v5 lite"]["hbm_bytes"]
    seq = traffic["seq_len"]
    for b in a.batch:
        ids = jax.ShapeDtypeStruct((b * a.chips, seq), jnp.int32,
                                   sharding=shard)
        for name, prog in (("base", step.base),
                           ("pulse", step.pulse_step))[:2 if a.pulse else 1]:
            t0 = time.monotonic()
            try:
                compiled = prog.lower(params, opt_state, (ids, ids)).compile()
            except Exception as e:  # noqa: BLE001 -- the compiler's refusal is the answer
                print(json.dumps({"per_chip_batch": b, "program": name,
                                  "refused": str(e)[:400]}), flush=True)
                continue
            m = compiled.memory_analysis()
            need = (m.argument_size_in_bytes + m.output_size_in_bytes
                    + m.temp_size_in_bytes - m.alias_size_in_bytes)
            txt = compiled.as_text()
            print(json.dumps({
                "per_chip_batch": b, "chips": a.chips, "program": name,
                "compile_s": round(time.monotonic() - t0, 1),
                "argument_gb": m.argument_size_in_bytes / 1e9,
                "output_gb": m.output_size_in_bytes / 1e9,
                "temp_gb": m.temp_size_in_bytes / 1e9,
                "need_gb": need / 1e9,
                "free_share": 1 - need / hbm,
                "kernels": {k: txt.count(k) for k in
                            ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                             "xent_fwd", "lm_head_fwd")},
                "all_reduce": txt.count(" all-reduce("),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
