"""VGG (11/16/19) — NHWC, bf16 compute, TPU-friendly.

Completes the reference's benchmark model-family trio: its harnesses
sweep ResNet-50 / VGG16 / BERT gradient sets
(``srcs/python/kungfu/tensorflow/v1/benchmarks/model_sizes.py``,
``srcs/python/kungfu/tensorflow/v1/benchmarks/__main__.py:112-120``) and
its fake-model tables carry ``vgg16-imagenet``
(``tests/go/fakemodel/fakemodel.go:12-17``).  Fresh implementation,
batch-norm variant included (VGG trains poorly in bf16 without it): plain
3x3 conv stacks + 2x2 maxpool, classifier head sized by ``num_classes``.

VGG's uniform 3x3/channel-doubling stacks are nearly all MXU work — the
historical "heavy" ImageNet model is a natural throughput payload
next to ResNet-50.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from kungfu_tpu.models import nn

# channels per conv layer, "M" = 2x2 maxpool (the classic configurations)
_CFGS = {
    11: (64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"),
    16: (64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
         512, 512, 512, "M", 512, 512, 512, "M"),
    19: (64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
         512, 512, 512, 512, "M", 512, 512, 512, 512, "M"),
}


def _maxpool2x2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


class VGG:
    def __init__(self, depth: int = 16, num_classes: int = 1000,
                 batch_norm: bool = True, hidden: int = 4096):
        if depth not in _CFGS:
            raise ValueError(f"depth must be one of {sorted(_CFGS)}")
        self.cfg = _CFGS[depth]
        self.num_classes = num_classes
        self.batch_norm = batch_norm
        self.hidden = hidden

    # -- init ------------------------------------------------------------
    def init(self, key) -> Tuple[dict, dict]:
        """Returns (params, bn_state); bn_state is empty without BN."""
        params, state = {}, {}
        in_ch = 3
        li = 0
        for c in self.cfg:
            if c == "M":
                continue
            key, k = jax.random.split(key)
            name = f"conv{li}"
            params[name] = nn.conv_init(k, in_ch, c, (3, 3),
                                        use_bias=not self.batch_norm)
            if self.batch_norm:
                params[f"{name}_bn"] = nn.batchnorm_init(c)
                state[f"{name}_bn"] = nn.batchnorm_state_init(c)
            in_ch = c
            li += 1
        # global-average-pooled head (the TF-era 7x7x512 flatten would pin
        # the input size; GAP keeps the model resolution-agnostic and
        # drops the 100M-param fc6 without changing the conv benchmark
        # profile)
        key, k1, k2 = jax.random.split(key, 3)
        params["fc1"] = nn.dense_init(k1, in_ch, self.hidden)
        params["head"] = nn.dense_init(k2, self.hidden, self.num_classes)
        return params, state

    # -- apply -----------------------------------------------------------
    def apply(self, params, state, x, train: bool = False,
              dtype=jnp.bfloat16, axis_name=None):
        """x: [N, H, W, 3] float.  Returns (logits_f32, new_state)."""
        new_state = {}
        h = x.astype(dtype)
        li = 0
        for c in self.cfg:
            if c == "M":
                h = _maxpool2x2(h)
                continue
            name = f"conv{li}"
            h = nn.conv_apply(params[name], h, dtype=dtype)
            if self.batch_norm:
                h, ns = nn.batchnorm_apply(
                    params[f"{name}_bn"], state[f"{name}_bn"], h, train,
                    axis_name=axis_name,
                )
                new_state[f"{name}_bn"] = ns
            h = jax.nn.relu(h)
            li += 1
        h = jnp.mean(h.astype(jnp.float32), axis=(1, 2))  # GAP
        h = jax.nn.relu(nn.dense_apply(params["fc1"], h))
        logits = nn.dense_apply(params["head"], h)
        return logits, new_state

    def loss(self, params, state, batch, train: bool = True,
             dtype=jnp.bfloat16, axis_name=None):
        x, y = batch
        logits, new_state = self.apply(
            params, state, x, train=train, dtype=dtype, axis_name=axis_name
        )
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=1).squeeze(1)
        return jnp.mean(nll), new_state


def vgg16(num_classes: int = 1000) -> VGG:
    return VGG(16, num_classes)
