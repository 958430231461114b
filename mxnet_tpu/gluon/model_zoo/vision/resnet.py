"""ResNet V1/V2 (ref: python/mxnet/gluon/model_zoo/vision/resnet.py —
BasicBlockV1/V2, BottleneckV1/V2, ResNetV1/V2, resnet18_v1 ... resnet152_v2).

The flagship config-2 benchmark path: hybridize() compiles the whole network
into one XLA program; convs hit the MXU via lax.conv_general_dilated in NCHW;
bf16 via net.cast('bfloat16') (AMP).
"""
from __future__ import annotations

import jax

from ...block import HybridBlock
from ... import nn

__all__ = ["ResNetV1", "ResNetV2", "BasicBlockV1", "BasicBlockV2",
           "BottleneckV1", "BottleneckV2",
           "resnet18_v1", "resnet34_v1", "resnet50_v1", "resnet101_v1",
           "resnet152_v1", "resnet18_v2", "resnet34_v2", "resnet50_v2",
           "resnet101_v2", "resnet152_v2", "get_resnet"]


def _conv3x3(channels, stride, in_channels, layout="NCHW"):
    return nn.Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                     use_bias=False, in_channels=in_channels, layout=layout)


def _bn_axis(layout):
    # channel axis for BatchNorm: 1 channel-first, -1 channel-last (NHWC is
    # the TPU-preferred layout: lanes = features, no copies around convs)
    return -1 if layout[-1] == "C" else 1


class BasicBlockV1(HybridBlock):
    """ref: class BasicBlockV1.

    ``fused=True`` (NHWC only) folds the bn1+relu pair into the second
    conv via the Pallas NormReluConv2D kernel (PERF.md: the normalized
    activation never reaches HBM)."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self._fused = fused
        if fused:
            assert layout == "NHWC", "fused resnet blocks need NHWC"
            self.conv1 = _conv3x3(channels, stride, in_channels, layout)
            self.f2 = nn.NormReluConv2D(channels, 3, in_channels=channels)
            self.bn2 = nn.BatchNorm(axis=ax)
        else:
            self.body = nn.HybridSequential(prefix="")
            self.body.add(_conv3x3(channels, stride, in_channels, layout))
            self.body.add(nn.BatchNorm(axis=ax))
            self.body.add(nn.Activation("relu"))
            self.body.add(_conv3x3(channels, 1, channels, layout))
            self.body.add(nn.BatchNorm(axis=ax))
        if downsample:
            self.downsample = nn.HybridSequential(prefix="")
            self.downsample.add(nn.Conv2D(channels, kernel_size=1, strides=stride,
                                          use_bias=False, in_channels=in_channels,
                                          layout=layout))
            self.downsample.add(nn.BatchNorm(axis=ax))
        else:
            self.downsample = None

    def forward(self, x):
        from .... import ndarray as F
        residual = x
        if self._fused:
            x = self.bn2(self.f2(self.conv1(x)))
        else:
            x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return F.Activation(x + residual, act_type="relu")


class BottleneckV1(HybridBlock):
    """ref: class BottleneckV1 (the ResNet-50 block).

    ``fused=True`` (NHWC only) folds bn1+relu into the 3×3 and bn2+relu
    into the closing 1×1 via the Pallas NormReluConv2D kernel — the two
    largest activations of the block never reach HBM (PERF.md)."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self._fused = fused
        if fused:
            assert layout == "NHWC", "fused resnet blocks need NHWC"
            self.conv1 = nn.Conv2D(channels // 4, kernel_size=1,
                                   strides=stride, use_bias=False,
                                   layout=layout)
            self.f2 = nn.NormReluConv2D(channels // 4, 3,
                                        in_channels=channels // 4)
            self.f3 = nn.NormReluConv2D(channels, 1,
                                        in_channels=channels // 4)
            self.bn3 = nn.BatchNorm(axis=ax)
        else:
            self.body = nn.HybridSequential(prefix="")
            self.body.add(nn.Conv2D(channels // 4, kernel_size=1, strides=stride,
                                    use_bias=False, layout=layout))
            self.body.add(nn.BatchNorm(axis=ax))
            self.body.add(nn.Activation("relu"))
            self.body.add(_conv3x3(channels // 4, 1, channels // 4, layout))
            self.body.add(nn.BatchNorm(axis=ax))
            self.body.add(nn.Activation("relu"))
            self.body.add(nn.Conv2D(channels, kernel_size=1, strides=1,
                                    use_bias=False, layout=layout))
            self.body.add(nn.BatchNorm(axis=ax))
        if downsample:
            self.downsample = nn.HybridSequential(prefix="")
            self.downsample.add(nn.Conv2D(channels, kernel_size=1, strides=stride,
                                          use_bias=False, in_channels=in_channels,
                                          layout=layout))
            self.downsample.add(nn.BatchNorm(axis=ax))
        else:
            self.downsample = None

    def forward(self, x):
        from .... import ndarray as F
        residual = x
        if self._fused:
            x = self.bn3(self.f3(self.f2(self.conv1(x))))
        else:
            x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return F.Activation(x + residual, act_type="relu")


class BasicBlockV2(HybridBlock):
    """ref: class BasicBlockV2 (pre-activation)."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.bn1 = nn.BatchNorm(axis=ax)
        self.conv1 = _conv3x3(channels, stride, in_channels, layout)
        self.bn2 = nn.BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels, 1, channels, layout)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        from .... import ndarray as F
        residual = x
        x = self.bn1(x)
        x = F.Activation(x, act_type="relu")
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x)
        x = F.Activation(x, act_type="relu")
        x = self.conv2(x)
        return x + residual


class BottleneckV2(HybridBlock):
    """ref: class BottleneckV2."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.bn1 = nn.BatchNorm(axis=ax)
        self.conv1 = nn.Conv2D(channels // 4, kernel_size=1, strides=1,
                               use_bias=False, layout=layout)
        self.bn2 = nn.BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels // 4, stride, channels // 4, layout)
        self.bn3 = nn.BatchNorm(axis=ax)
        self.conv3 = nn.Conv2D(channels, kernel_size=1, strides=1,
                               use_bias=False, layout=layout)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        from .... import ndarray as F
        residual = x
        x = self.bn1(x)
        x = F.Activation(x, act_type="relu")
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x)
        x = F.Activation(x, act_type="relu")
        x = self.conv2(x)
        x = self.bn3(x)
        x = F.Activation(x, act_type="relu")
        x = self.conv3(x)
        return x + residual


class _Stage(nn.HybridSequential):
    """One stage of residual blocks, its ops named ``stage<i>/...`` in the
    compiled program (``jax.named_scope``), so a device profile can say
    which stage a fusion belongs to.  Parameter names are the prefix's, as
    for the plain ``HybridSequential`` this stands in for."""

    def __init__(self, prefix, params=None):
        super().__init__(prefix=prefix, params=params)
        self._scope = prefix.rstrip("_").rsplit("_", 1)[-1]

    def forward(self, x, *args):
        with jax.named_scope(self._scope):
            return super().forward(x, *args)


class ResNetV1(HybridBlock):
    """ref: class ResNetV1."""

    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        self._layout = layout
        ax = _bn_axis(layout)
        with self.name_scope():
            self.features = nn.HybridSequential(prefix="")
            if thumbnail:
                self.features.add(_conv3x3(channels[0], 1, 0, layout))
            else:
                self.features.add(nn.Conv2D(channels[0], 7, 2, 3, use_bias=False,
                                            layout=layout))
                self.features.add(nn.BatchNorm(axis=ax))
                self.features.add(nn.Activation("relu"))
                self.features.add(nn.MaxPool2D(3, 2, 1, layout=layout))
            for i, num_layer in enumerate(layers):
                stride = 1 if i == 0 else 2
                self.features.add(self._make_layer(
                    block, num_layer, channels[i + 1], stride, i + 1,
                    in_channels=channels[i], layout=layout, fused=fused))
            self.features.add(nn.GlobalAvgPool2D(layout=layout))
            self.output = nn.Dense(classes, in_units=channels[-1])

    def _make_layer(self, block, layers, channels, stride, stage_index,
                    in_channels=0, layout="NCHW", fused=False):
        kw = {"fused": fused} if fused else {}
        layer = _Stage(prefix=f"stage{stage_index}_")
        with layer.name_scope():
            layer.add(block(channels, stride, channels != in_channels,
                            in_channels=in_channels, layout=layout,
                            prefix="", **kw))
            for _ in range(layers - 1):
                layer.add(block(channels, 1, False, in_channels=channels,
                                layout=layout, prefix="", **kw))
        return layer

    def forward(self, x):
        x = self.features(x)
        x = self.output(x)
        return x


class ResNetV2(HybridBlock):
    """ref: class ResNetV2."""

    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        self._layout = layout
        ax = _bn_axis(layout)
        with self.name_scope():
            self.features = nn.HybridSequential(prefix="")
            self.features.add(nn.BatchNorm(axis=ax, scale=False, center=False))
            if thumbnail:
                self.features.add(_conv3x3(channels[0], 1, 0, layout))
            else:
                self.features.add(nn.Conv2D(channels[0], 7, 2, 3, use_bias=False,
                                            layout=layout))
                self.features.add(nn.BatchNorm(axis=ax))
                self.features.add(nn.Activation("relu"))
                self.features.add(nn.MaxPool2D(3, 2, 1, layout=layout))
            in_channels = channels[0]
            for i, num_layer in enumerate(layers):
                stride = 1 if i == 0 else 2
                self.features.add(self._make_layer(
                    block, num_layer, channels[i + 1], stride, i + 1,
                    in_channels=in_channels, layout=layout))
                in_channels = channels[i + 1]
            self.features.add(nn.BatchNorm(axis=ax))
            self.features.add(nn.Activation("relu"))
            self.features.add(nn.GlobalAvgPool2D(layout=layout))
            self.features.add(nn.Flatten())
            self.output = nn.Dense(classes, in_units=in_channels)

    _make_layer = ResNetV1._make_layer

    def forward(self, x):
        x = self.features(x)
        x = self.output(x)
        return x


# ref: resnet.py — resnet_spec
resnet_spec = {
    18: ("basic_block", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
    34: ("basic_block", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
    50: ("bottle_neck", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
    101: ("bottle_neck", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
    152: ("bottle_neck", [3, 8, 36, 3], [64, 256, 512, 1024, 2048]),
}
resnet_net_versions = [ResNetV1, ResNetV2]
resnet_block_versions = [
    {"basic_block": BasicBlockV1, "bottle_neck": BottleneckV1},
    {"basic_block": BasicBlockV2, "bottle_neck": BottleneckV2},
]


def get_resnet(version, num_layers, pretrained=False, ctx=None, root=None,
               **kwargs):
    """ref: get_resnet."""
    assert num_layers in resnet_spec, f"invalid depth {num_layers}"
    assert version in (1, 2)
    block_type, layers, channels = resnet_spec[num_layers]
    resnet_class = resnet_net_versions[version - 1]
    block_class = resnet_block_versions[version - 1][block_type]
    net = resnet_class(block_class, layers, channels, **kwargs)
    if pretrained:
        raise RuntimeError("pretrained weights unavailable in this "
                           "zero-egress environment; load_parameters() from "
                           "a local file instead")
    return net


def resnet18_v1(**kwargs):
    return get_resnet(1, 18, **kwargs)


def resnet34_v1(**kwargs):
    return get_resnet(1, 34, **kwargs)


def resnet50_v1(**kwargs):
    return get_resnet(1, 50, **kwargs)


def resnet101_v1(**kwargs):
    return get_resnet(1, 101, **kwargs)


def resnet152_v1(**kwargs):
    return get_resnet(1, 152, **kwargs)


def resnet18_v2(**kwargs):
    return get_resnet(2, 18, **kwargs)


def resnet34_v2(**kwargs):
    return get_resnet(2, 34, **kwargs)


def resnet50_v2(**kwargs):
    return get_resnet(2, 50, **kwargs)


def resnet101_v2(**kwargs):
    return get_resnet(2, 101, **kwargs)


def resnet152_v2(**kwargs):
    return get_resnet(2, 152, **kwargs)
