#!/usr/bin/env python3
"""Tile sweep for the flash-attention Pallas kernels on the local chip.

Each candidate runs one jitted forward + backward under the profiler and
is reported by what the device spent in each kernel (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) and in everything else of the call
(``around_ms``: the rowsum behind ``delta``, pads, layout copies), read
from the trace by ``kfbench/lib/trace.py`` -- the reduction the
benchmark's ``flash_ms_per_step`` goes through.  ``auto`` is what
``attention.tile_plan`` chooses for the shape; ``--run-elements`` sweeps
``attention.RUN_ELEMENTS``, how many score elements one product covers.  ``--root`` times another
checkout's kernels (a parent commit unpacked under ``_export/``) in the
same process, on the same chip.  Needs a TPU; exits non-zero without one.

    python benchmarks/flash_sweep.py --shape 64,1024,64 \
        --blocks auto,128:128,256:256 [--root _export/parent] [--noncausal]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def load_attention(root):
    """The attention module of the checkout at ``root`` (this one's if
    None), under a name of its own so that two can be timed side by side."""
    if root is None:
        from kungfu_tpu.ops.pallas import attention
        return attention
    path = os.path.join(root, "kungfu_tpu", "ops", "pallas", "attention.py")
    spec = importlib.util.spec_from_file_location(
        "attention_" + str(abs(hash(path))), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", default=[],
                    help="BH,S,D (repeatable); default 64,1024,64")
    ap.add_argument("--blocks", default="auto",
                    help="comma list of auto or bq:bk")
    ap.add_argument("--root", action="append", default=[],
                    help="also time this checkout's kernels (repeatable)")
    ap.add_argument("--noncausal", action="store_true")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--check", action="store_true",
                    help="compare values and gradients with XLA's attention")
    ap.add_argument("--run-elements", default="",
                    help="comma list of values for attention.RUN_ELEMENTS "
                         "(score elements one product may cover)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip (interpret mode): no times")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "flash_sweep.jsonl"))
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kfbench.lib import trace
    from kungfu_tpu.models.transformer import default_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        print(f"flash_sweep: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    causal = not a.noncausal
    shapes = [tuple(int(x) for x in s.split(",")) for s in a.shape] or [
        (64, 1024, 64)]
    blocks = [(None, None) if b == "auto" else
              tuple(int(x) for x in b.split(":")) for b in a.blocks.split(",")]
    impls = [("here", load_attention(None))] + [
        (r, load_attention(r)) for r in a.root]
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    rows = []

    for bh, s, d in shapes:
        rng = np.random.default_rng(s + d)
        q, k, v, w = (jnp.asarray(rng.standard_normal((bh, s, d)),
                                  jnp.bfloat16) for _ in range(4))
        ref = None
        if a.check:
            def ref_loss(q, k, v):
                o = default_attention(q[None], k[None], v[None], causal)[0]
                return jnp.sum(o.astype(jnp.float32) * w), o
            ref = jax.jit(jax.grad(ref_loss, (0, 1, 2), has_aux=True))(
                *(t.astype(jnp.float32) for t in (q, k, v)))
        for name, mod in impls:
            for bq, bk, run in (
                    [(bq, bk, int(x) if x else None)
                     for x in a.run_elements.split(",") for bq, bk in blocks]
                    if name == "here" else [(None, None, None)]):
                row = {"impl": name, "device": dev.device_kind,
                       "shape": [bh, s, d], "causal": causal,
                       "block_q": bq, "block_k": bk}
                if run is not None:
                    mod.RUN_ELEMENTS = run
                if hasattr(mod, "tile_plan"):
                    row["plan"] = list(mod.tile_plan(s, d, q.dtype, causal,
                                                     bq, bk))

                def loss(q, k, v, mod=mod, bq=bq, bk=bk):
                    o = mod.flash_attention(q, k, v, causal=causal,
                                            block_q=bq, block_k=bk)
                    return jnp.sum(o.astype(jnp.float32) * w), o

                step = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))
                try:
                    got = jax.block_until_ready(step(q, k, v))
                except Exception as e:  # noqa: BLE001 -- the compiler's refusal is the answer
                    row["refused"] = str(e)[:300]
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                    continue
                if ref is not None:
                    (gq, gk, gv), o = got
                    (rq, rk, rv), ro = ref
                    row["max_err"] = {
                        n: float(jnp.max(jnp.abs(x.astype(jnp.float32) - y)))
                        for n, x, y in (("o", o, ro), ("dq", gq, rq),
                                        ("dk", gk, rk), ("dv", gv, rv))}
                with tempfile.TemporaryDirectory() as tdir:
                    jax.profiler.start_trace(tdir)
                    for _ in range(a.calls):
                        got = step(q, k, v)
                    jax.block_until_ready(got)
                    jax.profiler.stop_trace()
                    t = trace.load(trace.find_xplane(tdir))
                if not t.devices:       # a rehearsal: no device, no times
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                    continue
                for kern in KERNELS:
                    row[kern + "_ms"] = 1e3 * trace.op_seconds(
                        t, r"^\S*" + kern + r"(?![a-z])") / a.calls
                busy = 1e3 * trace.busy_seconds(t) / a.calls
                row["flash_ms"] = sum(row[kern + "_ms"] for kern in KERNELS)
                row["around_ms"] = busy - row["flash_ms"]
                rows.append(row)
                print(json.dumps(row), flush=True)
    with open(a.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
