"""Operations of GN-LeNet per training sample, from its shapes.

Counts the convolutions and matrix products at two operations per
multiply-add, a convolution only over the taps that fall inside the image
(SAME padding adds zeros, which need no operation); GroupNorm, ReLU,
pooling, bias adds and the loss are left out (about 1% of the total).  A
training step needs the forward pass, the weight gradients of every layer
(one forward's worth each) and the input gradients of every layer but the
first (its input is the data).
"""
from __future__ import annotations


def _taps(size: int, k: int = 5) -> int:
    """Kernel taps inside the image, summed over one axis's SAME outputs."""
    half = k // 2
    return sum(min(i + half, size - 1) - max(i - half, 0) + 1 for i in range(size))


def layer_flops(m) -> dict:
    """Forward operations per sample of each layer."""
    w, c, k = m["width"], m["channels"], m["num_classes"]
    return {
        "conv1": 2 * c * w * _taps(32) ** 2,
        "conv2": 2 * w * (2 * w) * _taps(16) ** 2,
        "fc1": 2 * (2 * w) * 8 * 8 * 128,
        "fc2": 2 * 128 * k,
    }


def forward_flops(m) -> int:
    return sum(layer_flops(m).values())


def train_flops(m) -> int:
    """Forward plus backward operations per sample."""
    per = layer_flops(m)
    return 3 * sum(per.values()) - per["conv1"]
