"""Plain reference of ViT-S/4, the model of the vits4-* configurations.

Straight ``jax.numpy`` with nothing of the program imported, after DeiT-S
(arXiv 2012.12877): 4x4 patches of a 32x32 image, each flattened in (row,
column, channel) order and mapped linearly to the width; a class token in
front and learned positions added; 12 pre-LayerNorm blocks of multi-head
self-attention (one qkv matrix with bias, its output laid out as q, k, v
and within each the heads of 64, scores scaled by head size^-1/2, softmax
over the keys, output projection) and an MLP of 4x the width with the
exact GELU; LayerNorm (eps 1e-6) of the class token and a linear head;
the mean cross-entropy.  Under a width of 64 there is one head of the
whole width.

The weights are drawn from the seed by the rule the configuration's model
states: ``split(key, 5)`` for the patch embedding, class token, positions,
head and blocks, the blocks' key split in four for the stacked (12, ...)
qkv, projection, fc1 and fc2 weights; each a truncated normal on [-2, 2]
scaled by 0.02; zero biases, unit LayerNorm scales.

``dtype`` is the precision everything is computed and kept in: float32 at
``Precision.HIGHEST`` for the reference, bfloat16 for its control.  Each
block is rematerialised for the backward pass (``jax.checkpoint``), so
that 16 nodes' gradients fit beside the state the check holds; that
changes what is stored, not what is computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DEPTH = 12
PATCH = 4
EPS = 1e-6
STD = 0.02


def init(key, width: int, channels: int, num_classes: int, dtype=jnp.float32):
    k_patch, k_cls, k_pos, k_head, k_blocks = jax.random.split(key, 5)
    k_qkv, k_proj, k_fc1, k_fc2 = jax.random.split(k_blocks, 4)
    tokens = (32 // PATCH) ** 2 + 1
    hidden = 4 * width

    def tn(k, shape):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * STD).astype(dtype)

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    def ln(*shape):
        return {"g": jnp.ones(shape, dtype), "b": zeros(*shape)}

    return {
        "patch_embed": {"w": tn(k_patch, (PATCH * PATCH * channels, width)), "b": zeros(width)},
        "cls": tn(k_cls, (width,)),
        "pos": tn(k_pos, (tokens, width)),
        "blocks": {
            "ln1": ln(DEPTH, width),
            "qkv": {"w": tn(k_qkv, (DEPTH, width, 3 * width)), "b": zeros(DEPTH, 3 * width)},
            "proj": {"w": tn(k_proj, (DEPTH, width, width)), "b": zeros(DEPTH, width)},
            "ln2": ln(DEPTH, width),
            "fc1": {"w": tn(k_fc1, (DEPTH, width, hidden)), "b": zeros(DEPTH, hidden)},
            "fc2": {"w": tn(k_fc2, (DEPTH, hidden, width)), "b": zeros(DEPTH, width)},
        },
        "norm": ln(width),
        "head": {"w": tn(k_head, (width, num_classes)), "b": zeros(num_classes)},
    }


def _precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(jnp.asarray(2.0, x.dtype))))


def _block(x, p, prec):
    b, t, d = x.shape
    heads = max(1, d // 64)
    hd = d // heads
    dot = lambda a, w: jnp.dot(a, w, precision=prec)
    y = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
    qkv = (dot(y, p["qkv"]["w"]) + p["qkv"]["b"]).reshape(b, t, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) / jnp.sqrt(
        jnp.asarray(hd, x.dtype))
    a = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    a = a / jnp.sum(a, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=prec).reshape(b, t, d)
    x = x + dot(o, p["proj"]["w"]) + p["proj"]["b"]
    y = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
    return x + dot(_gelu(dot(y, p["fc1"]["w"]) + p["fc1"]["b"]), p["fc2"]["w"]) + p["fc2"]["b"]


def apply(params, images):
    dtype = params["head"]["w"].dtype
    prec = _precision(dtype)
    b, h, w, c = images.shape
    x = images.astype(dtype).reshape(b, h // PATCH, PATCH, w // PATCH, PATCH, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, -1, PATCH * PATCH * c)
    x = jnp.dot(x, params["patch_embed"]["w"], precision=prec) + params["patch_embed"]["b"]
    cls = jnp.broadcast_to(params["cls"], (b, 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"]
    block = jax.checkpoint(_block, static_argnums=(2,))
    for layer in range(DEPTH):
        x = block(x, jax.tree_util.tree_map(lambda a: a[layer], params["blocks"]), prec)
    y = _layer_norm(x[:, 0], params["norm"]["g"], params["norm"]["b"])
    return jnp.dot(y, params["head"]["w"], precision=prec) + params["head"]["b"]


def loss(params, images, labels):
    logits = apply(params, images)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)
