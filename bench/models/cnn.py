"""The paper's FEMNIST CNN (arXiv:2106.06627 §4.2: "2-layer CNN with a
hidden size of 64"), written plainly: weights in the program's layout, the
float reference forward and loss, and the operations one sample needs.

Layout: conv1 5x5x1x(hidden/2) and conv2 5x5x(hidden/2)xhidden, SAME padding,
each followed by ReLU and a 2x2 max pool, then one dense layer to the
classes. Leaves ``conv1, b1, conv2, b2, fc, bf``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _trunc(key, fan_in, shape, dtype):
    std = 1.0 / jnp.sqrt(jnp.float32(max(fan_in, 1)))
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


def shapes(cfg: dict) -> dict:
    h, c, s = cfg["hidden"], cfg["channels"], cfg["image_size"]
    flat = (s // 4) ** 2 * h
    return {"conv1": (5, 5, c, h // 2), "b1": (h // 2,),
            "conv2": (5, 5, h // 2, h), "b2": (h,),
            "fc": (flat, cfg["num_classes"]), "bf": (cfg["num_classes"],)}


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Truncated-normal fan-in weights, zero biases."""
    sh = shapes(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {"conv1": _trunc(k1, 25 * cfg["channels"], sh["conv1"], dtype),
            "b1": jnp.zeros(sh["b1"], dtype),
            "conv2": _trunc(k2, 25 * (cfg["hidden"] // 2), sh["conv2"], dtype),
            "b2": jnp.zeros(sh["b2"], dtype),
            "fc": _trunc(k3, sh["fc"][0], sh["fc"], dtype),
            "bf": jnp.zeros(sh["bf"], dtype)}


def _conv_relu_pool(x, w, b):
    y = jax.lax.conv_general_dilated(x, w.astype(x.dtype), (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = jax.nn.relu(y + b.astype(x.dtype))
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def logits(params, x):
    x = x.astype(params["conv1"].dtype)
    y = _conv_relu_pool(x, params["conv1"], params["b1"])
    y = _conv_relu_pool(y, params["conv2"], params["b2"])
    y = y.reshape(y.shape[0], -1)
    return (y @ params["fc"] + params["bf"]).astype(jnp.float32)


def loss(params, x, y, mask):
    """Mean negative log-likelihood over the real (mask 1) samples."""
    logp = jax.nn.log_softmax(logits(params, x))
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def accuracy(params, x, y, mask):
    hit = (jnp.argmax(logits(params, x), axis=-1) == y).astype(jnp.float32)
    return jnp.sum(hit * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def forward_flops_per_sample(cfg: dict) -> int:
    """Multiply-adds x 2 of one forward pass: the two convolutions at their
    SAME output sizes and the dense layer (bias, ReLU and pooling are not
    counted)."""
    h, c, s = cfg["hidden"], cfg["channels"], cfg["image_size"]
    conv1 = s * s * (h // 2) * 25 * c * 2
    conv2 = (s // 2) ** 2 * h * 25 * (h // 2) * 2
    fc = (s // 4) ** 2 * h * cfg["num_classes"] * 2
    return conv1 + conv2 + fc


def train_flops_per_sample(cfg: dict) -> int:
    """Forward and backward: three times the forward."""
    return 3 * forward_flops_per_sample(cfg)
