"""Operations an item of training needs, computed from the
configuration's sizes. Kept with the benchmark so that no PR that claims
a gain can change what a token or an image is counted as. Recomputed
operations (remat) do not count.
"""

from __future__ import annotations


def lm_train_flops_per_token(config: dict, settings: dict) -> float:
    """``6 N + 12 L T H``: N the parameters of the matrix multiplications
    (4 H^2 attention + 2 H (ratio H) MLP per layer, plus the H x V output
    head; the embedding lookup is no multiplication), times 2 for the
    multiply-add and 3 for forward and backward; plus the PaLM attention
    term at sequence length T. Copy of
    ``benchmarks/llm_mfu_bench.py:lm_flops_per_token``."""
    hidden, layers = config["n_embd"], config["n_layer"]
    ratio = config.get("mlp_ratio", 4)
    n_matmul = layers * (4 * hidden * hidden + 2 * hidden * ratio * hidden) \
        + hidden * config["vocab_size"]
    return 6.0 * n_matmul + 12.0 * layers * settings["seq_len"] * hidden


def _conv_macs(cin, cout, k, out_hw):
    return cin * cout * k * k * out_hw * out_hw


def resnet50_forward_macs(image: int = 224, classes: int = 1000) -> int:
    """Multiply-adds of one forward pass of ResNet-50 (bottleneck,
    shortcut type B: a 1x1 projection where the shape changes), counted
    from the layer shapes; convolutions and the classifier only. The
    stride of a stage sits on the bottleneck's 3x3 convolution."""
    hw = image // 2                                   # conv1 7x7 / 2
    macs = _conv_macs(3, 64, 7, hw)
    hw //= 2                                          # max-pool 3x3 / 2
    cin = 64
    for planes, blocks, stride in ((64, 3, 1), (128, 4, 2),
                                   (256, 6, 2), (512, 3, 2)):
        for b in range(blocks):
            s = stride if b == 0 else 1
            out_hw = hw // s
            macs += _conv_macs(cin, planes, 1, hw)            # 1x1 reduce
            macs += _conv_macs(planes, planes, 3, out_hw)     # 3x3 (stride)
            macs += _conv_macs(planes, planes * 4, 1, out_hw)  # 1x1 expand
            if b == 0:                                # projection shortcut
                macs += _conv_macs(cin, planes * 4, 1, out_hw)
            cin, hw = planes * 4, out_hw
    return macs + cin * classes


def resnet50_train_flops_per_image(config: dict, settings: dict) -> float:
    """Forward multiply-adds x 2 operations x 3 (forward, and a backward
    pass of twice the forward)."""
    return 6.0 * resnet50_forward_macs(config["image_size"],
                                       config["num_classes"])
