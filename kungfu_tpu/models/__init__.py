"""Model zoo + minimal functional NN library.

The reference framework carries no model code (it moves gradient buffers;
models live in its examples/benchmarks: MNIST SLP/CNN examples, and the
ResNet-50/VGG16/BERT *size lists* used by its benchmark harnesses —
``srcs/python/kungfu/tensorflow/v1/benchmarks/model_sizes.py``,
``tests/go/fakemodel/fakemodel.go:12-17``).  The TPU build ships real
models because they are its benchmark workload:

* :mod:`kungfu_tpu.models.nn` — tiny functional layer library (explicit
  param pytrees, pure apply fns — jit/shard_map friendly, bf16-first).
* :mod:`kungfu_tpu.models.mlp` — MNIST SLP/MLP (the reference's minimum
  end-to-end example, ``examples/tf1_mnist_session.py``).
* :mod:`kungfu_tpu.models.resnet` — ResNet-50 (v1.5), NHWC, bf16 compute.
* :mod:`kungfu_tpu.models.vgg` — VGG-16 (the reference benchmark trio's
  second ImageNet family), NHWC, bf16, optional sync-BN.
* :mod:`kungfu_tpu.models.transformer` — GPT-style transformer (the
  flagship; BERT-base-sized config included), ring-attention capable.
* :mod:`kungfu_tpu.models.cohere2_moe` — the ``cohere2_moe`` decoder
  (window and full attention layers, grouped-query heads, a sparse
  expert layer beside the attention), served by ``serve/engine.py``;
  :mod:`kungfu_tpu.models.experts` is its expert layer.
* :mod:`kungfu_tpu.models.pangu_moe` — the ``pangu_ultra_moe`` decoder
  (latent attention in two orders, sandwich norms, a dense layer before
  the expert layers, which are ``experts`` again), served likewise.
* :mod:`kungfu_tpu.models.solar_open2` — the ``solar_open2`` decoder
  (gated delta-rule linear-attention layers, whose past is a matrix a
  head and not rows a position, beside a gated softmax layer in four;
  ``experts`` again), served likewise.
* :mod:`kungfu_tpu.models.evabyte` — the ``evabyte`` decoder (a
  byte-level model with EVA attention: exact softmax inside an aligned
  window, every chunk of positions before it pooled into one learned
  key/value row, one softmax over both), served likewise.
* :mod:`kungfu_tpu.models.phi4flash` — the ``phi4flash`` decoder
  (SambaY: selective state-space layers beside window and full
  differential attention, then Gated Memory Units and cross-attention
  layers that read the one full layer's keys and values), served
  likewise.
* :mod:`kungfu_tpu.models.fake` — gradient-shaped fake models for
  collective benchmarking without real compute (parity with
  ``tests/go/fakemodel``).
"""

from kungfu_tpu.models import nn
from kungfu_tpu.models.cohere2_moe import Cohere2Moe, Cohere2MoeConfig
from kungfu_tpu.models.evabyte import EvaByte, EvaByteConfig
from kungfu_tpu.models.mlp import MLP, mnist_slp
from kungfu_tpu.models.pangu_moe import PanguMoe, PanguMoeConfig
from kungfu_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
from kungfu_tpu.models.resnet import ResNet, resnet50
from kungfu_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
from kungfu_tpu.models.transformer import Transformer, TransformerConfig, bert_base, gpt_small
from kungfu_tpu.models.vgg import VGG, vgg16
from kungfu_tpu.models.fake import fake_model_sizes, fake_grads

__all__ = [
    "nn",
    "Cohere2Moe",
    "Cohere2MoeConfig",
    "EvaByte",
    "EvaByteConfig",
    "MLP",
    "PanguMoe",
    "PanguMoeConfig",
    "Phi4Flash",
    "Phi4FlashConfig",
    "mnist_slp",
    "ResNet",
    "resnet50",
    "SolarOpen2",
    "SolarOpen2Config",
    "VGG",
    "vgg16",
    "Transformer",
    "TransformerConfig",
    "bert_base",
    "gpt_small",
    "fake_model_sizes",
    "fake_grads",
]
