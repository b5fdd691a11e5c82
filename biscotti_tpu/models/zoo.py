"""Model zoo — capability parity with ML/Pytorch/*.py plus the numpy logreg.

  softmax    linear d_in→k                (ref: softmax_model.py:7-24; mnist 7,850 params)
  logreg     L2 binary logistic, y∈{−1,1} (ref: ML/code/logistic_model.py:92-106)
  mnist_cnn  conv(1→16,5,pad 4)+relu+fc   (ref: mnist_cnn_model.py:7-41, "ONE LAYER")
  cifar_cnn  LeNet-5 shape                 (ref: cifar_cnn_model.py; BASELINE.md "CIFAR LeNet")
  lfw_cnn    small conv net over 62×47×3   (ref: lfw_cnn_model.py)
  svm        linear + multiclass hinge     (ref: svm_model.py)
  laguna_s_fedlora  Laguna-S-2.1's sparse-expert, window/full-attention decoder
             (models/laguna.py): a frozen 3.0 B-parameter share at the
             published widths, rank-16 adapters trained, d = 1,048,576
  laguna_tiny  the same mechanism at the CPU tests' size
  deepseek_v2_fedlora  DeepSeek-V2's latent-attention (MLA), group-routed
             sparse-expert decoder (models/deepseek_v2.py): a frozen 5.2
             B-parameter share at the published widths, rank-16 adapters on
             the five attention projections trained, d = 5,166,080; reads
             the dataset `lm_tokens_dsv2` (25,600 classes)
  deepseek_v2_tiny  the same mechanism at the CPU tests' size
             (`lm_tokens_tiny`, as laguna_tiny)
  granite_h_micro_fedlora  Granite-4.0-H-Micro's Mamba-2 / attention hybrid
             (models/granite_hybrid.py), WHOLE: 3.2 B frozen parameters (40
             layers, 36 of them state-space, a tied embedding of 100,352
             rows), rank-16 adapters on in_proj / out_proj and q, k, v, o
             trained, d = 6,410,240; reads the dataset `lm_tokens_granite`
  granite_h_tiny  the same mechanism at the CPU tests' size
             (`lm_tokens_tiny`; four chunks a window)
  qwen3_next_fedlora  Qwen3-Next-80B-A3B-Instruct's gated delta-net / gated
             attention hybrid (models/qwen3_next.py): a frozen 5.4
             B-parameter share at the published widths (12 layers, 9 of them
             the delta rule, 128 of 512 experts a layer, 37,984 rows of an
             untied vocabulary), rank-16 adapters on in_proj_qkvz /
             out_proj and q, k, v, o trained, d = 2,605,056; reads the
             dataset `lm_tokens_qwen3next`
  qwen3_next_tiny  the same mechanism at the CPU tests' size
             (`lm_tokens_tiny`; two periods, four chunks a window)
  mimo_v2_fedlora  MiMo-V2.5's window / full attention decoder
             (models/mimo_v2.py): a frozen 5.8 B-parameter share at the
             published widths (7 layers: the dense one and a whole period of
             window x 4, full, window; 32 of 256 experts a layer, 19,072
             rows of an untied vocabulary), rank-16 adapters on the fused
             qkv and on o trained, d = 2,080,768; reads the dataset
             `lm_tokens_mimo` (windows of 2,048 tokens)
  mimo_v2_tiny  the same mechanism at the CPU tests' size
             (`lm_tokens_tiny`; a window of 4 with its sink, head groups of
             4 and 2)
  olmo_hybrid_fedlora  Olmo-Hybrid-7B's gated delta-net / plain full
             attention hybrid (models/olmo_hybrid.py): the first of two
             pipeline stages, 4.1 B frozen parameters at the published
             widths (16 layers, 12 of them the delta rule at heads of 96 |
             192 with beta in (0, 2), a dense SwiGLU on every layer, the
             whole untied vocabulary of 100,352 rows), rank-16 adapters on
             in_proj_qkvz / out_proj and q, k, v, o trained, d = 5,038,080;
             reads the dataset `lm_tokens_olmo`
  olmo_hybrid_tiny  the same mechanism at the CPU tests' size
             (`lm_tokens_tiny`; one period, four chunks a window)

Inits are MXU-friendly (fan-in scaled normal) and every model is expressed in
channels-last NHWC, the layout XLA prefers on TPU.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from biscotti_tpu.data.datasets import base_name, spec as dspec
from biscotti_tpu.models import (deepseek_v2, granite_hybrid, laguna,
                                 mimo_v2, olmo_hybrid, qwen3_next)
from biscotti_tpu.models.base import Model, cross_entropy, make_model, multiclass_hinge


def _linear_init(key, d_in, d_out):
    kw, _ = jax.random.split(key)
    s = 1.0 / math.sqrt(d_in)
    return {
        "w": jax.random.uniform(kw, (d_in, d_out), jnp.float32, -s, s),
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def softmax_model(d_in: int, n_classes: int) -> Model:
    def init(key):
        return _linear_init(key, d_in, n_classes)

    def apply(p, x):
        return x.reshape(x.shape[0], d_in) @ p["w"] + p["b"]

    def loss(p, x, y):
        return cross_entropy(apply(p, x), y)

    return make_model("softmax", d_in, n_classes, init, apply, loss)


def svm_model(d_in: int, n_classes: int) -> Model:
    def init(key):
        return _linear_init(key, d_in, n_classes)

    def apply(p, x):
        return x.reshape(x.shape[0], d_in) @ p["w"] + p["b"]

    def loss(p, x, y):
        return multiclass_hinge(apply(p, x), y)

    return make_model("svm", d_in, n_classes, init, apply, loss)


def logreg_model(d_in: int, lammy: float = 0.01) -> Model:
    """Binary L2 logistic regression on ±1 labels with a bias feature
    (ref: logistic_model.py:8-13,92-106; bias column added by utils.py)."""
    d = d_in + 1

    def init(key):
        return {"w": jnp.zeros((d,), jnp.float32)}

    def _with_bias(x):
        return jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], axis=1)

    def apply(p, x):
        # two-column logits so argmax-style error/accuracy code works unchanged
        z = _with_bias(x) @ p["w"]
        return jnp.stack([-z, z], axis=-1)

    def loss(p, x, y):
        # The reference's gradient is (1/B)·Xᵀres + λw (ref:
        # logistic_model.py:100-106 — data term batch-averaged, L2 term
        # NOT), so the loss whose gradient matches is
        # mean(logaddexp(0, −y·Xw)) + λ/2‖w‖², y∈{−1,1}.
        ypm = 2.0 * y.astype(jnp.float32) - 1.0
        z = _with_bias(x) @ p["w"]
        return jnp.mean(jnp.logaddexp(0.0, -ypm * z)) + 0.5 * lammy * jnp.dot(p["w"], p["w"])

    return make_model("logreg", d_in, 2, init, apply, loss, step_rule="sgd")


def _conv_init(key, shape):  # HWIO
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def mnist_cnn_model() -> Model:
    """conv(1→16, 5×5, stride 1, pad 4) + relu + fc(16·32·32→10)
    (ref: mnist_cnn_model.py:12-16,31-41 — the active "ONE LAYER" branch;
    MaxPool2d(1) is the identity, so it is omitted)."""

    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "conv": {"w": _conv_init(k1, (5, 5, 1, 16)), "b": jnp.zeros((16,), jnp.float32)},
            "fc": _linear_init(k2, 16 * 32 * 32, 10),
        }

    def apply(p, x):
        x = x.reshape(x.shape[0], 28, 28, 1)
        h = jax.lax.conv_general_dilated(
            x, p["conv"]["w"], window_strides=(1, 1), padding=[(4, 4), (4, 4)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["conv"]["b"]
        h = jax.nn.relu(h)
        h = h.reshape(h.shape[0], -1)
        return h @ p["fc"]["w"] + p["fc"]["b"]

    def loss(p, x, y):
        return cross_entropy(apply(p, x), y)

    return make_model("mnist_cnn", 784, 10, init, apply, loss)


def _lenet_apply(p, x, hw, chans):
    h = x.reshape(x.shape[0], *hw, chans)
    for name in ("c1", "c2"):
        h = jax.lax.conv_general_dilated(
            h, p[name]["w"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p[name]["b"]
        h = jax.nn.relu(h)
        h = jax.lax.reduce_window(
            h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    h = h.reshape(h.shape[0], -1)
    for name in ("f1", "f2"):
        h = jax.nn.relu(h @ p[name]["w"] + p[name]["b"])
    return h @ p["f3"]["w"] + p["f3"]["b"]


def cifar_cnn_model() -> Model:
    """LeNet-5: conv(3→6,5) pool conv(6→16,5) pool fc120 fc84 fc10
    (ref: cifar_cnn_model.py; BASELINE.md row "CIFAR LeNet")."""

    def init(key):
        ks = jax.random.split(key, 5)
        return {
            "c1": {"w": _conv_init(ks[0], (5, 5, 3, 6)), "b": jnp.zeros((6,), jnp.float32)},
            "c2": {"w": _conv_init(ks[1], (5, 5, 6, 16)), "b": jnp.zeros((16,), jnp.float32)},
            "f1": _linear_init(ks[2], 16 * 5 * 5, 120),
            "f2": _linear_init(ks[3], 120, 84),
            "f3": _linear_init(ks[4], 84, 10),
        }

    def apply(p, x):
        return _lenet_apply(p, x, (32, 32), 3)

    def loss(p, x, y):
        return cross_entropy(apply(p, x), y)

    return make_model("cifar_cnn", 3072, 10, init, apply, loss)


def lfw_cnn_model() -> Model:
    """Small LeNet-shape net over 62×47×3 gender/face classes (ref: lfw_cnn_model.py)."""

    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "c1": {"w": _conv_init(ks[0], (5, 5, 3, 6)), "b": jnp.zeros((6,), jnp.float32)},
            "c2": {"w": _conv_init(ks[1], (5, 5, 6, 16)), "b": jnp.zeros((16,), jnp.float32)},
            # 62×47 → conv5 VALID 58×43 → pool2 29×21 → conv5 VALID 25×17 → pool2 12×8
            "f1": _linear_init(ks[2], 16 * 12 * 8, 84),
            "f3": _linear_init(ks[3], 84, 12),
        }

    def apply(p, x):
        h = x.reshape(x.shape[0], 62, 47, 3)
        for name in ("c1", "c2"):
            h = jax.lax.conv_general_dilated(
                h, p[name]["w"], window_strides=(1, 1), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p[name]["b"]
            h = jax.nn.relu(h)
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(h @ p["f1"]["w"] + p["f1"]["b"])
        return h @ p["f3"]["w"] + p["f3"]["b"]

    def loss(p, x, y):
        return cross_entropy(apply(p, x), y)

    return make_model("lfw_cnn", 8742, 12, init, apply, loss)


MODELS: Dict[str, callable] = {
    "softmax": lambda ds: softmax_model(dspec(ds).d_in, dspec(ds).n_classes),
    "logreg": lambda ds: logreg_model(dspec(ds).d_in),
    "svm": lambda ds: svm_model(dspec(ds).d_in, dspec(ds).n_classes),
    "mnist_cnn": lambda ds: mnist_cnn_model(),
    "cifar_cnn": lambda ds: cifar_cnn_model(),
    "lfw_cnn": lambda ds: lfw_cnn_model(),
    **{name: (lambda ds, name=name, build=build, presets=presets:
              _language_model(build, name, presets[name], ds))
       for build, presets in ((laguna.laguna_model, laguna.PRESETS),
                              (deepseek_v2.deepseek_v2_model,
                               deepseek_v2.PRESETS),
                              (granite_hybrid.granite_hybrid_model,
                               granite_hybrid.PRESETS),
                              (qwen3_next.qwen3_next_model,
                               qwen3_next.PRESETS),
                              (mimo_v2.mimo_v2_model, mimo_v2.PRESETS),
                              (olmo_hybrid.olmo_hybrid_model,
                               olmo_hybrid.PRESETS))
       for name in presets},
}

# what a dataset trains where no model is named (softmax otherwise)
DEFAULTS = {"creditcard": "logreg", "lm_tokens": "laguna_s_fedlora",
            "lm_tokens_tiny": "laguna_tiny",
            "lm_tokens_dsv2": "deepseek_v2_fedlora",
            "lm_tokens_granite": "granite_h_micro_fedlora",
            "lm_tokens_qwen3next": "qwen3_next_fedlora",
            "lm_tokens_mimo": "mimo_v2_fedlora",
            "lm_tokens_olmo": "olmo_hybrid_fedlora"}


def _language_model(build, name: str, cfg, dataset: str) -> Model:
    spec = dspec(dataset)
    if not spec.tokens or spec.n_classes != cfg.vocab:
        raise ValueError(
            f"model {name!r} reads windows of token ids below {cfg.vocab}; "
            f"dataset {dataset!r} has {spec.n_classes} classes"
            + ("" if spec.tokens else " and no tokens"))
    return build(name, cfg, spec.d_in)


def model_for_dataset(dataset: str, model: str = "") -> Model:
    """Default model per dataset, mirroring the reference pairings
    (softmax for mnist/cifar/lfw via client_obj.init; logreg for creditcard
    via ML/code/logistic_model.py)."""
    return MODELS[model or DEFAULTS.get(base_name(dataset), "softmax")](
        dataset)
