"""ResNet (reference ``models/resnet/ResNet.scala:58``).

Covers both reference variants: CIFAR-10 basic-block ResNet-N (depth = 6n+2)
and ImageNet bottleneck ResNet-18/34/50/101/152 with shortcut type A/B/C.
Built as a Graph of SpatialConvolution/BatchNorm/ReLU — all MXU-shaped convs
fused by XLA. ``format`` selects the image layout: NCHW matches the
reference's default; NHWC is the TPU-preferred layout (channels ride the
128-wide lanes with no relayout) and is what the benchmark's training cell
uses. The default comes from ``Engine.default_data_format()``
(BIGDL_TPU_ENABLE_NHWC).
"""

from __future__ import annotations

import bigdl_tpu.nn as nn
from bigdl_tpu.utils.engine import default_data_format


def _conv_bn(x, n_in, n_out, k, stride, pad, name, fmt, with_relu=True):
    x = nn.SpatialConvolution(n_in, n_out, k, k, stride, stride, pad, pad,
                              with_bias=False, format=fmt).set_name(name)(x)
    x = nn.SpatialBatchNormalization(n_out, format=fmt).set_name(
        name + "_bn")(x)
    if with_relu:
        x = nn.ReLU().set_name(name + "_relu")(x)
    return x


def _shortcut(x, n_in, n_out, stride, shortcut_type, name, fmt):
    if n_in != n_out or stride != 1:
        if shortcut_type == "A":
            # identity with zero-padded channels: approximate with 1x1 conv
            # (type A is parameter-free in the paper; the reference's CIFAR
            # default); we keep B-style projection for XLA friendliness
            shortcut_type = "B"
        if shortcut_type in ("B", "C"):
            s = nn.SpatialConvolution(
                n_in, n_out, 1, 1, stride, stride, with_bias=False,
                format=fmt).set_name(name + "_proj")(x)
            return nn.SpatialBatchNormalization(n_out, format=fmt).set_name(
                name + "_proj_bn")(s)
    elif shortcut_type == "C":
        s = nn.SpatialConvolution(n_in, n_out, 1, 1, 1, 1, with_bias=False,
                                  format=fmt).set_name(name + "_proj")(x)
        return nn.SpatialBatchNormalization(n_out, format=fmt).set_name(
            name + "_proj_bn")(s)
    return x


def _basic_block(x, n_in, n_out, stride, shortcut_type, name, fmt):
    s = _shortcut(x, n_in, n_out, stride, shortcut_type, name, fmt)
    y = _conv_bn(x, n_in, n_out, 3, stride, 1, name + "_conv1", fmt)
    y = _conv_bn(y, n_out, n_out, 3, 1, 1, name + "_conv2", fmt,
                 with_relu=False)
    out = nn.CAddTable().set_name(name + "_add")(y, s)
    return nn.ReLU().set_name(name + "_out")(out)


def _bottleneck(x, n_in, planes, stride, shortcut_type, name, fmt):
    n_out = planes * 4
    s = _shortcut(x, n_in, n_out, stride, shortcut_type, name, fmt)
    y = _conv_bn(x, n_in, planes, 1, 1, 0, name + "_conv1", fmt)
    y = _conv_bn(y, planes, planes, 3, stride, 1, name + "_conv2", fmt)
    y = _conv_bn(y, planes, n_out, 1, 1, 0, name + "_conv3", fmt,
                 with_relu=False)
    out = nn.CAddTable().set_name(name + "_add")(y, s)
    return nn.ReLU().set_name(name + "_out")(out)


_IMAGENET_CFGS = {
    18: ("basic", [2, 2, 2, 2]),
    34: ("basic", [3, 4, 6, 3]),
    50: ("bottleneck", [3, 4, 6, 3]),
    101: ("bottleneck", [3, 4, 23, 3]),
    152: ("bottleneck", [3, 8, 36, 3]),
}


def ResNet(class_num=1000, depth=50, shortcut_type="B", data_set="ImageNet",
           format=None):
    """Build ResNet (reference ``ResNet.apply``, ``models/resnet/ResNet.scala:58``)."""
    fmt = format or default_data_format()
    if data_set.lower().startswith("cifar"):
        return _cifar_resnet(class_num, depth, shortcut_type, fmt)
    block_type, stages = _IMAGENET_CFGS[depth]
    inp = nn.Input()
    x = _conv_bn(inp, 3, 64, 7, 2, 3, "conv1", fmt)
    x = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1, format=fmt).set_name(
        "pool1")(x)
    n_in = 64
    planes = [64, 128, 256, 512]
    for si, (n_blocks, p) in enumerate(zip(stages, planes)):
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            name = f"res{si + 2}_{bi}"
            if block_type == "bottleneck":
                x = _bottleneck(x, n_in, p, stride, shortcut_type, name, fmt)
                n_in = p * 4
            else:
                x = _basic_block(x, n_in, p, stride, shortcut_type, name, fmt)
                n_in = p
    x = nn.SpatialAveragePooling(7, 7, global_pooling=True,
                                 format=fmt).set_name("pool5")(x)
    x = nn.Reshape((n_in,)).set_name("flatten")(x)
    x = nn.Linear(n_in, class_num).set_name("fc")(x)
    out = nn.LogSoftMax().set_name("prob")(x)
    return nn.Graph(inp, out)


def _cifar_resnet(class_num, depth, shortcut_type, fmt):
    assert (depth - 2) % 6 == 0, "CIFAR depth must be 6n+2"
    n = (depth - 2) // 6
    inp = nn.Input()
    x = _conv_bn(inp, 3, 16, 3, 1, 1, "conv1", fmt)
    n_in = 16
    for si, p in enumerate([16, 32, 64]):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            x = _basic_block(x, n_in, p, stride, shortcut_type,
                             f"res{si + 2}_{bi}", fmt)
            n_in = p
    x = nn.SpatialAveragePooling(8, 8, global_pooling=True, format=fmt)(x)
    x = nn.Reshape((64,))(x)
    x = nn.Linear(64, class_num)(x)
    out = nn.LogSoftMax()(x)
    return nn.Graph(inp, out)
