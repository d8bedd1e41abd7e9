"""The paper's CNN (2 conv + ``num_fc`` fc, FLrce section 4.1): its part of
the benchmark, found by the configs' ``"model": "PaperCNN"``.

A model module gives the harness what it needs of one architecture:

* ``program_model(cfg, frozen)``: the program's model object, which
  ``bench/run.py`` hands to ``run_federated`` (the only function here that
  imports the program);
* ``init(cfg, key)``: the trained parameters, float32; ``frozen(cfg, key)``:
  the weights that are not trained (None here);
* ``num_classes(cfg)`` and ``sampler(cfg, mix, rng)``: the labels' range and
  the features drawn for given labels (``bench/traffic.py``);
* ``loss`` and ``correct``: the plain reference's per-batch loss and count of
  correct answers (``bench/reference.py``), in ``jax.numpy``;
* ``flops_per_sample(cfg)``: model FLOPs to train on one sample, for
  ``round_mfu``.

Samples are synthetic class-conditional images: Gaussian class centres
pushed through a frozen random two-layer teacher, reshaped to
``(side, side, channels)``, a copy of the program's ``make_classification``
and ``make_image_like``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

TEACHER_HIDDEN = 64


def num_classes(cfg: Dict) -> int:
    return int(cfg["num_classes"])


def program_model(cfg: Dict, frozen: None):
    from repro.models.cnn import PaperCNN

    return PaperCNN(side=int(cfg["side"]), channels=int(cfg["channels"]),
                    num_classes=int(cfg["num_classes"]), num_fc=int(cfg["num_fc"]),
                    conv_channels=tuple(cfg["conv_channels"]), fc_width=int(cfg["fc_width"]))


# -- weights -------------------------------------------------------------------
def init(cfg: Dict, key: jax.Array) -> Dict:
    """He-normal weights and zero biases, in the layout the program's
    ``PaperCNN`` takes (``conv1``, ``conv2``, ``fc`` list), float32."""
    c1, c2 = (int(v) for v in cfg["conv_channels"])
    k, ch, s = int(cfg["conv_kernel"]), int(cfg["channels"]), int(cfg["side"])
    flat = (s // 4) * (s // 4) * c2
    dims = [flat] + [int(cfg["fc_width"])] * (int(cfg["num_fc"]) - 1) + [int(cfg["num_classes"])]
    keys = jax.random.split(key, 2 + len(dims) - 1)

    def he(key, shape, fan_in):
        return math.sqrt(2.0 / fan_in) * jax.random.normal(key, shape, jnp.float32)

    return {
        "conv1": {"w": he(keys[0], (k, k, ch, c1), k * k * ch), "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": he(keys[1], (k, k, c1, c2), k * k * c1), "b": jnp.zeros((c2,), jnp.float32)},
        "fc": [
            {"w": he(keys[2 + i], (a, b), a), "b": jnp.zeros((b,), jnp.float32)}
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))
        ],
    }


def frozen(cfg: Dict, key: jax.Array) -> None:
    """Every weight of the CNN is trained."""
    return None


# -- samples -------------------------------------------------------------------
def feature_dim(cfg: Dict) -> int:
    return int(cfg["side"]) ** 2 * int(cfg["channels"])


def _teacher_draw(rng: np.random.Generator, feature_dim: int, num_classes: int):
    """The teacher, drawn and scaled as ``make_classification`` draws it."""
    w1 = rng.normal(size=(feature_dim, TEACHER_HIDDEN)) / np.sqrt(feature_dim)
    w2 = rng.normal(size=(TEACHER_HIDDEN, feature_dim)) / np.sqrt(TEACHER_HIDDEN)
    centers = rng.normal(size=(num_classes, feature_dim)) * 1.8
    return w1, w2, centers


def sampler(cfg: Dict, mix: Dict, rng: np.random.Generator):
    """Draws the teacher from ``rng``; returns ``draw(labels)``, which draws
    those labels' images from ``rng``, (n, side, side, channels) float32."""
    w1, w2, centers = (a.astype(np.float32) for a in
                       _teacher_draw(rng, feature_dim(cfg), num_classes(cfg)))
    noise = float(mix["noise"])
    shape = (-1, int(cfg["side"]), int(cfg["side"]), int(cfg["channels"]))

    def draw(labels):
        z = centers[labels]
        z += noise * rng.standard_normal(z.shape, dtype=np.float32)
        x = np.tanh(z @ w1) @ w2
        x += 0.1 * z
        return x.reshape(shape)

    return draw


# -- the plain reference ---------------------------------------------------------
def logits(params, x, prec):
    h = x
    for name in ("conv1", "conv2"):
        h = lax.conv_general_dilated(
            h, params[name]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
        ) + params[name]["b"]
        h = jnp.maximum(h, 0)
        n, hh, ww, ch = h.shape
        h = h.reshape(n, hh // 2, 2, ww // 2, 2, ch).max(axis=(2, 4))   # 2x2 max-pool
    h = h.reshape(h.shape[0], -1)
    fc = params["fc"]
    for i, lyr in enumerate(fc):
        h = jnp.dot(h, lyr["w"], precision=prec) + lyr["b"]
        if i < len(fc) - 1:
            h = jnp.maximum(h, 0)
    return h.astype(jnp.float32)


def _images(cfg: Dict, x, dtype):
    return x.reshape((-1, int(cfg["side"]), int(cfg["side"]), int(cfg["channels"]))).astype(dtype)


def loss(cfg: Dict, params, frozen: None, x, y, w, dtype, prec):
    """Mean cross-entropy of one batch's rows (flat features ``x``, labels
    ``y``) weighted by ``w``; ``params`` in ``dtype``."""
    logp = jax.nn.log_softmax(logits(params, _images(cfg, x, dtype), prec), axis=-1)
    per = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def correct(cfg: Dict, params, frozen: None, x, y, dtype, prec):
    """How many of the rows' top logits are their labels."""
    lg = logits(params, _images(cfg, x, dtype), prec)
    return jnp.sum((jnp.argmax(lg, axis=-1) == y).astype(jnp.int32))


# -- the yardstick's count -------------------------------------------------------
def _dims(cfg: Dict) -> Tuple[int, int, int, int, int, List[int]]:
    s, c = int(cfg["side"]), int(cfg["channels"])
    c1, c2 = (int(v) for v in cfg["conv_channels"])
    k = int(cfg["conv_kernel"])
    flat = (s // 4) * (s // 4) * c2
    fc = [flat] + [int(cfg["fc_width"])] * (int(cfg["num_fc"]) - 1) + [int(cfg["num_classes"])]
    return s, c, c1, c2, k, fc


def param_count(cfg: Dict) -> int:
    """Weights and biases of the 2-conv + fc CNN."""
    s, c, c1, c2, k, fc = _dims(cfg)
    conv = (k * k * c * c1 + c1) + (k * k * c1 * c2 + c2)
    return conv + sum(a * b + b for a, b in zip(fc[:-1], fc[1:]))


def flops_per_sample(cfg: Dict) -> float:
    """Model FLOPs to train on one sample: the forward pass's
    multiply-adds (2 FLOPs each) of both SAME-padded convolutions and the
    fc stack, times 3 for forward plus backward.  Pooling, activations,
    biases and the loss are left out, as is any padding the program adds."""
    s, c, c1, c2, k, fc = _dims(cfg)
    conv1 = 2 * s * s * k * k * c * c1
    conv2 = 2 * (s // 2) * (s // 2) * k * k * c1 * c2
    dense = sum(2 * a * b for a, b in zip(fc[:-1], fc[1:]))
    return 3.0 * (conv1 + conv2 + dense)
