"""Plain reference of ResNet-50 (He et al. 2015, bottleneck blocks,
projection shortcuts where the shape changes, the stride of a stage on
the bottleneck's 3x3 convolution): the TRAINING-mode forward pass and
the cross-entropy loss in straightforward ``jax.numpy`` / ``lax`` and
float32 at the highest precision. Batch normalisation uses the batch's
own statistics, as the first training iteration does. It reads the
program's parameter tree and nothing else of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def _ordered(tree):
    """The children that have parameters, in the order they were added:
    keys are ``"<index>:<ClassName><n>"``."""
    return [tree[k] for k in sorted(tree, key=lambda k: int(k.split(":")[0]))
            if tree[k]]


def _conv(x, p, stride, pad):
    return lax.conv_general_dilated(
        x, p["weight"], (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(x, p):
    mean = jnp.mean(x, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), (0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + BN_EPS) \
        * p["weight"][None, :, None, None] + p["bias"][None, :, None, None]


def _convs_and_bns(block):
    layers = _ordered(block)
    return layers[0::2], layers[1::2]          # conv, bn, conv, bn, ...


def logits(params, images):
    """``images``: (B, 3, 224, 224) float32 -> (B, classes)."""
    nodes = _ordered(params)
    stem_conv, stem_bn, *blocks, head = nodes
    x = jax.nn.relu(_bn(_conv(images, stem_conv, 2, 3), stem_bn))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    blocks = iter(blocks)
    for main in blocks:
        convs, bns = _convs_and_bns(main)
        # a block that changes the shape is followed by its projection;
        # every stage but the first (64 planes in) opens with stride 2
        out_planes, in_planes = convs[2]["weight"].shape[0], x.shape[1]
        stride = 2 if in_planes not in (64, out_planes) else 1
        shortcut = x
        if in_planes != out_planes:
            (sc_conv,), (sc_bn,) = _convs_and_bns(next(blocks))
            shortcut = _bn(_conv(x, sc_conv, stride, 0), sc_bn)
        h = jax.nn.relu(_bn(_conv(x, convs[0], 1, 0), bns[0]))
        h = jax.nn.relu(_bn(_conv(h, convs[1], stride, 1), bns[1]))
        h = _bn(_conv(h, convs[2], 1, 0), bns[2])
        x = jax.nn.relu(h + shortcut)
    x = jnp.mean(x, (2, 3))                     # 7x7 average pool
    return x @ head["weight"].T + head["bias"]


def mean_cross_entropy(params, images, labels, config):
    """Mean of ``logsumexp(logits) - logits[label]``, labels 1-based."""
    with jax.default_matmul_precision("highest"):
        z = logits(params, images)
    picked = jnp.take_along_axis(z, labels[:, None] - 1, axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, -1) - picked)
