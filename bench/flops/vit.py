"""Operations of ViT-S/4 per training sample, from its shapes.

Counts the matrix products at two operations per multiply-add: per block
the qkv, projection and two MLP matrices over every token (12 width^2
multiply-adds a token), the attention scores and their weighted values
(tokens^2 width multiply-adds each), the patch embedding over the 64 patches and the
head over the class token.  LayerNorm, softmax, GELU, bias and residual
adds and the loss are left out (XLA counts 2.1% more at width 384).  A
training step needs the forward pass, the weight gradients of every layer
(one forward's worth each) and the input gradients of every layer but the
patch embedding (its input is the data).  The forward pass that the
rematerialised blocks repeat in the backward pass is not counted: it is
work the model does not need.
"""
from __future__ import annotations

DEPTH, PATCH, IMAGE = 12, 4, 32


def layer_flops(m) -> dict:
    """Forward operations per sample of each layer (blocks summed)."""
    w, c, k = m["width"], m["channels"], m["num_classes"]
    patches = (IMAGE // PATCH) ** 2
    tokens = patches + 1
    return {
        "patch_embed": 2 * patches * PATCH * PATCH * c * w,
        "block_matmuls": DEPTH * 2 * tokens * 12 * w * w,
        "attention": DEPTH * 2 * 2 * tokens * tokens * w,
        "head": 2 * w * k,
    }


def forward_flops(m) -> int:
    return sum(layer_flops(m).values())


def train_flops(m) -> int:
    """Forward plus backward operations per sample."""
    per = layer_flops(m)
    return 3 * sum(per.values()) - per["patch_embed"]
