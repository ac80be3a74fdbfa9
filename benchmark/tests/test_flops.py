"""The operation counts against hand-worked values."""

from benchmark import flops, harness

GPT2M = harness.load_json(harness.HERE / "configs" / "gpt2-medium.json")
RESNET = harness.load_json(harness.HERE / "configs" / "resnet50.json")


def test_gpt2_medium_flops_per_token():
    want = 6 * (24 * 12 * 1024 ** 2 + 1024 * 50257) \
        + 12 * 24 * 1024 * 1024
    assert want == 2_422_708_224
    assert flops.lm_train_flops_per_token(GPT2M, GPT2M["train"]) == want


def test_resnet50_forward_macs():
    # conv1: 3 x 64 x 7 x 7 at 112 x 112
    assert flops._conv_macs(3, 64, 7, 112) == 118_013_952
    # first bottleneck of stage 1 at 56 x 56: 64->64 1x1, 64->64 3x3,
    # 64->256 1x1 and the 64->256 projection
    hw = 56 * 56
    first = (64 * 64 + 64 * 64 * 9 + 64 * 256 + 64 * 256) * hw
    assert first == 231_211_008
    # the published figure for ResNet-50 with the stride on the 3x3
    # convolution is 4.1 G multiply-adds
    total = flops.resnet50_forward_macs()
    assert total == 4_089_184_256
    assert flops.resnet50_train_flops_per_image(RESNET, RESNET["train"]) \
        == 6.0 * total
