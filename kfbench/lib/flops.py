"""Operations and bytes, from shapes: the benchmark's own arithmetic,
kept where no PR that claims a gain can change it.

Only what the algorithm requires is counted: a causal attention needs
the lower triangle, S (S + 1) / 2 of the S * S scores; recomputation
(remat) is never counted.  A matrix product of [m, k] by [k, n] is
2 m k n operations.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations: four attention matrices and
    two feed-forward matrices a layer, and the (untied) output head."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + d * cfg["vocab_size"]


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward for one token of a ``seq``-token causal
    sequence: forward is 2 per matrix parameter plus the two attention
    products (QK^T and PV, 2 * head_dim each per attended pair, all
    heads: 4 * n_embd per pair, per layer); backward is twice that."""
    attn = 4 * cfg["n_embd"] * cfg["n_layer"] * causal_pairs(seq) / seq
    return 3.0 * (2 * matmul_params(cfg) + attn)


#: products of [S, D] x [D, S] or [S, S] x [S, D] size that one call of
#: each flash kernel needs: forward QK^T, PV; dq: QK^T, dO V^T, dS K;
#: dkv: QK^T, P^T dO, dO V^T, dS^T Q
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
#: [S, D] arrays each call reads or writes (q k v o; q k v do dq; q k v do dk dv)
FLASH_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}


def flash_call(kernel: str, heads: int, seq: int, head_dim: int,
               itemsize: int = 2):
    """(operations, bytes) of one call of a flash kernel over ``heads``
    (batch * heads) causal [seq, head_dim] problems."""
    ops = FLASH_PRODUCTS[kernel] * 2 * causal_pairs(seq) * head_dim * heads
    byts = FLASH_ARRAYS[kernel] * seq * head_dim * itemsize * heads
    return ops, byts


def roofline_seconds(ops: float, byts: float, peaks: dict):
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = byts / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
