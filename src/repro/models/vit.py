"""ViT-S/4 — a vision transformer at the widths of DeiT-S (Touvron et al.,
"Training data-efficient image transformers", arXiv 2012.12877, Table 1)
with 4x4 patches, for 32x32 images.

Published and fixed here: 12 pre-LayerNorm blocks (LayerNorm eps 1e-6 with
scale and bias), multi-head self-attention with a qkv bias and heads of 64,
an exact-GELU MLP of 4x the width, a learned class token and learned
absolute positions (no RoPE); the class token's final LayerNorm feeds a
linear head.  ``width`` 384 gives DeiT-S: 6 heads, MLP 1,536, 65 tokens,
21,342,346 parameters.  Narrower widths (the CPU tests) keep one head of
the whole width where the width is under 64.

The blocks' parameters are stacked on a leading layer axis and run under
``lax.scan``; each block is rematerialised (``jax.checkpoint``): the
backward pass recomputes a block's activations from its input instead of
keeping them, with the same arithmetic.

Initial weights, from one key: ``split(key, 5)`` gives the keys of the
patch embedding, the class token, the positions, the head and the blocks;
the blocks' key splits in four for the stacked qkv, projection, fc1 and fc2
weights.  Every weight, the class token and the positions are a truncated
normal on [-2, 2] scaled by 0.02; biases are zero, LayerNorm scales one.

Named scopes mark the layers in the compiled program's op metadata:
``patch_embed``, ``attention`` (qkv, scores, softmax, values, projection),
``mlp`` (fc1, GELU, fc2) and ``head``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.common import dense_init

DEPTH = 12
HEAD_DIM = 64
MLP_RATIO = 4
PATCH = 4
IMAGE = 32
EPS = 1e-6
INIT_STD = 0.02


def _ln_params(shape, dtype):
    return {"g": jnp.ones(shape, dtype), "b": jnp.zeros(shape, dtype)}


def vit_init(key, num_classes: int = 10, channels: int = 3, width: int = 384,
             dtype=jnp.float32):
    tokens = (IMAGE // PATCH) ** 2 + 1
    hidden = MLP_RATIO * width
    k_patch, k_cls, k_pos, k_head, k_blocks = jax.random.split(key, 5)
    k_qkv, k_proj, k_fc1, k_fc2 = jax.random.split(k_blocks, 4)

    def w(k, shape):
        return dense_init(k, shape, dtype, scale=INIT_STD)

    def linear(k, shape):
        return {"w": w(k, shape), "b": jnp.zeros(shape[:-2] + shape[-1:], dtype)}

    return {
        "patch_embed": linear(k_patch, (PATCH * PATCH * channels, width)),
        "cls": w(k_cls, (width,)),
        "pos": w(k_pos, (tokens, width)),
        "blocks": {
            "ln1": _ln_params((DEPTH, width), dtype),
            "qkv": linear(k_qkv, (DEPTH, width, 3 * width)),
            "proj": linear(k_proj, (DEPTH, width, width)),
            "ln2": _ln_params((DEPTH, width), dtype),
            "fc1": linear(k_fc1, (DEPTH, width, hidden)),
            "fc2": linear(k_fc2, (DEPTH, hidden, width)),
        },
        "norm": _ln_params((width,), dtype),
        "head": linear(k_head, (width, num_classes)),
    }


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["g"] + p["b"]


def _attention(p, x, heads):
    b, t, d = x.shape
    hd = d // heads
    qkv = (x @ p["qkv"]["w"] + p["qkv"]["b"]).reshape(b, t, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, d)
    return out @ p["proj"]["w"] + p["proj"]["b"]


def _mlp(p, x):
    h = jax.nn.gelu(x @ p["fc1"]["w"] + p["fc1"]["b"], approximate=False)
    return h @ p["fc2"]["w"] + p["fc2"]["b"]


def _block(x, p, heads):
    y = layer_norm(x, p["ln1"])
    with jax.named_scope("attention"):
        y = _attention(p, y, heads)
    x = x + y
    y = layer_norm(x, p["ln2"])
    with jax.named_scope("mlp"):
        y = _mlp(p, y)
    return x + y, None


def vit_apply(params, images):
    """images: (B, 32, 32, C) NHWC -> logits (B, num_classes)."""
    b, h, w, c = images.shape
    width = params["cls"].shape[-1]
    with jax.named_scope("patch_embed"):
        # non-overlapping 4x4 patches, each flattened in (row, column,
        # channel) order: a 4x4 convolution at stride 4
        x = images.reshape(b, h // PATCH, PATCH, w // PATCH, PATCH, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, -1, PATCH * PATCH * c)
        x = x @ params["patch_embed"]["w"] + params["patch_embed"]["b"]
        cls = jnp.broadcast_to(params["cls"], (b, 1, width))
        x = jnp.concatenate([cls, x], axis=1) + params["pos"]
    block = jax.checkpoint(functools.partial(_block, heads=max(1, width // HEAD_DIM)),
                           prevent_cse=False)
    x, _ = jax.lax.scan(block, x, params["blocks"])
    with jax.named_scope("head"):
        return layer_norm(x[:, 0], params["norm"]) @ params["head"]["w"] + params["head"]["b"]
