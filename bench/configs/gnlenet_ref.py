"""Plain reference of GN-LeNet, the model of the gnlenet-* configurations.

Straight ``jax.numpy``/``lax`` with nothing of the program imported:
conv 5x5 (SAME) -> GroupNorm(8) -> ReLU -> 2x2 max-pool, twice, then
fc-128 -> ReLU -> fc-classes, and the mean cross-entropy.  The weights are
drawn from the seed by the same rule the configuration's model states
(per node: four keys split from the node's key; truncated normal on
[-2, 2] scaled by fan-in^-1/2, 5x5xC fan-in for the convolutions; zero
biases, unit GroupNorm scales).

``dtype`` is the precision everything is computed and kept in: float32 at
``Precision.HIGHEST`` for the reference, bfloat16 for its control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUPS = 8
EPS = 1e-5


def init(key, width: int, channels: int, num_classes: int, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    c1, c2 = width, 2 * width

    def tn(k, shape, std):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)

    def zeros(n):
        return jnp.zeros((n,), dtype)

    return {
        "conv1": {"w": tn(ks[0], (5, 5, channels, c1), (25 * channels) ** -0.5),
                  "b": zeros(c1), "g": jnp.ones((c1,), dtype), "be": zeros(c1)},
        "conv2": {"w": tn(ks[1], (5, 5, c1, c2), (25 * c1) ** -0.5),
                  "b": zeros(c2), "g": jnp.ones((c2,), dtype), "be": zeros(c2)},
        "fc1": {"w": tn(ks[2], (c2 * 8 * 8, 128), (c2 * 8 * 8) ** -0.5), "b": zeros(128)},
        "fc2": {"w": tn(ks[3], (128, num_classes), 128 ** -0.5), "b": zeros(num_classes)},
    }


def _precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _group_norm(x, g, be):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, GROUPS, c // GROUPS)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + EPS)
    return xg.reshape(b, h, w, c) * g + be


def _block(x, p, prec):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=prec) + p["b"]
    y = jnp.maximum(_group_norm(y, p["g"], p["be"]), 0)
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply(params, images):
    dtype = params["fc2"]["w"].dtype
    prec = _precision(dtype)
    x = _block(images.astype(dtype), params["conv1"], prec)
    x = _block(x, params["conv2"], prec)
    x = x.reshape(x.shape[0], -1)
    x = jnp.maximum(jnp.dot(x, params["fc1"]["w"], precision=prec) + params["fc1"]["b"], 0)
    return jnp.dot(x, params["fc2"]["w"], precision=prec) + params["fc2"]["b"]


def loss(params, images, labels):
    logits = apply(params, images)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)
