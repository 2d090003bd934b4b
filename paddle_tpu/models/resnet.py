"""ResNet family + SE-ResNeXt.

Parity targets: the reference's ResNet DP benchmark config
and the dist_se_resnext.py distributed fixture
(/root/reference/python/paddle/fluid/tests/unittests/dist_se_resnext.py).

TPU notes: batch norm is the reference implementation's main non-fusable
op; here it is plain jnp so XLA fuses it into the surrounding convs.
Convs stay NCHW at the API level (XLA relayouts for the MXU).
"""

import jax.numpy as jnp

from .. import nn


class ConvBNLayer(nn.Layer):
    def __init__(self, in_ch, out_ch, filter_size, stride=1, groups=1,
                 act=None, data_format="NCHW", dtype="float32"):
        super().__init__(dtype=dtype)
        self.conv = nn.Conv2D(in_ch, out_ch, filter_size, stride=stride,
                              padding=(filter_size - 1) // 2, groups=groups,
                              bias_attr=False, data_format=data_format,
                              dtype=dtype)
        self.bn = nn.BatchNorm(out_ch, act=act, data_format=data_format,
                               dtype=dtype)

    def forward(self, x):
        return self.bn(self.conv(x))


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, in_ch, ch, stride=1, data_format="NCHW",
                 dtype="float32"):
        super().__init__(dtype=dtype)
        df = data_format
        self.conv0 = ConvBNLayer(in_ch, ch, 3, stride=stride, act="relu",
                                 data_format=df, dtype=dtype)
        self.conv1 = ConvBNLayer(ch, ch, 3, data_format=df, dtype=dtype)
        self.short = (None if stride == 1 and in_ch == ch else
                      ConvBNLayer(in_ch, ch, 1, stride=stride,
                                  data_format=df, dtype=dtype))
        self.relu = nn.ReLU()

    def forward(self, x):
        y = self.conv1(self.conv0(x))
        s = x if self.short is None else self.short(x)
        return self.relu(y + s)


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, in_ch, ch, stride=1, data_format="NCHW",
                 dtype="float32"):
        super().__init__(dtype=dtype)
        df = data_format
        self.conv0 = ConvBNLayer(in_ch, ch, 1, act="relu", data_format=df,
                                 dtype=dtype)
        self.conv1 = ConvBNLayer(ch, ch, 3, stride=stride, act="relu",
                                 data_format=df, dtype=dtype)
        self.conv2 = ConvBNLayer(ch, ch * 4, 1, data_format=df, dtype=dtype)
        self.short = (None if stride == 1 and in_ch == ch * 4 else
                      ConvBNLayer(in_ch, ch * 4, 1, stride=stride,
                                  data_format=df, dtype=dtype))
        self.relu = nn.ReLU()

    def forward(self, x):
        y = self.conv2(self.conv1(self.conv0(x)))
        s = x if self.short is None else self.short(x)
        return self.relu(y + s)


class ResNet(nn.Layer):
    """data_format="NHWC" runs the whole conv stack channels-last (the
    MXU-preferred layout — no XLA relayout transposes); the input API
    stays NCHW with ONE transpose at the stem."""

    def __init__(self, block, depths, num_classes=1000, in_ch=3,
                 data_format="NCHW", dtype="float32"):
        super().__init__(dtype=dtype)
        self._data_format = data_format
        self.stem = ConvBNLayer(in_ch, 64, 7, stride=2, act="relu",
                                data_format=data_format, dtype=dtype)
        self.pool = nn.MaxPool2D(3, 2, padding=1,
                                 data_format=data_format)
        chans = [64, 128, 256, 512]
        blocks = []
        prev = 64
        for stage, (ch, depth) in enumerate(zip(chans, depths)):
            for i in range(depth):
                stride = 2 if i == 0 and stage > 0 else 1
                blocks.append(block(prev, ch, stride=stride,
                                    data_format=data_format, dtype=dtype))
                prev = ch * block.expansion
        self.blocks = nn.LayerList(blocks)
        self.global_pool = nn.Pool2D(pool_type="avg", global_pooling=True,
                                     data_format=data_format)
        self.fc = nn.Linear(prev, num_classes, dtype=dtype)

    def forward(self, x):
        if self._data_format == "NHWC":
            x = jnp.transpose(x, (0, 2, 3, 1))   # NCHW API -> NHWC core
        x = self.pool(self.stem(x))
        for b in self.blocks:
            x = b(x)
        x = self.global_pool(x)
        return self.fc(x.reshape(x.shape[0], -1))


def set_bn_stats_sample(model, stats_sample):
    """Set ghost-batch BN stats subsampling on every BatchNorm in the
    model (see the batch_norm kernel: the stats passes are ~25% of the
    on-chip ResNet-50 step, almost all HBM traffic that a k/N
    subsample divides by N/k)."""
    for layer in model.sublayers(include_self=True):
        if isinstance(layer, nn.BatchNorm):
            layer._stats_sample = stats_sample
    return model


def resnet18(num_classes=1000, data_format="NCHW", dtype="float32",
             bn_stats_sample=0):
    return set_bn_stats_sample(
        ResNet(BasicBlock, [2, 2, 2, 2], num_classes,
               data_format=data_format, dtype=dtype), bn_stats_sample)


def resnet34(num_classes=1000, data_format="NCHW", dtype="float32",
             bn_stats_sample=0):
    return set_bn_stats_sample(
        ResNet(BasicBlock, [3, 4, 6, 3], num_classes,
               data_format=data_format, dtype=dtype), bn_stats_sample)


def resnet50(num_classes=1000, data_format="NCHW", dtype="float32",
             bn_stats_sample=0):
    return set_bn_stats_sample(
        ResNet(BottleneckBlock, [3, 4, 6, 3], num_classes,
               data_format=data_format, dtype=dtype), bn_stats_sample)


class SEBlock(nn.Layer):
    """Squeeze-and-excitation gate."""

    def __init__(self, ch, reduction=16, dtype="float32"):
        super().__init__(dtype=dtype)
        self.squeeze = nn.Pool2D(pool_type="avg", global_pooling=True)
        self.fc1 = nn.Linear(ch, ch // reduction, act="relu", dtype=dtype)
        self.fc2 = nn.Linear(ch // reduction, ch, act="sigmoid", dtype=dtype)

    def forward(self, x):
        s = self.squeeze(x).reshape(x.shape[0], -1)
        s = self.fc2(self.fc1(s))
        return x * s.reshape(s.shape[0], s.shape[1], 1, 1)


class SEResNeXtBlock(nn.Layer):
    def __init__(self, in_ch, ch, stride=1, cardinality=32, reduction=16,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        self.conv0 = ConvBNLayer(in_ch, ch, 1, act="relu", dtype=dtype)
        self.conv1 = ConvBNLayer(ch, ch, 3, stride=stride,
                                 groups=cardinality, act="relu", dtype=dtype)
        self.conv2 = ConvBNLayer(ch, ch * 2, 1, dtype=dtype)
        self.se = SEBlock(ch * 2, reduction, dtype=dtype)
        self.short = (None if stride == 1 and in_ch == ch * 2 else
                      ConvBNLayer(in_ch, ch * 2, 1, stride=stride,
                                  dtype=dtype))
        self.relu = nn.ReLU()

    def forward(self, x):
        y = self.se(self.conv2(self.conv1(self.conv0(x))))
        s = x if self.short is None else self.short(x)
        return self.relu(y + s)


class SEResNeXt(nn.Layer):
    """SE-ResNeXt-50 32x4d — the reference's hardest dist fixture."""

    def __init__(self, num_classes=1000, depths=(3, 4, 6, 3), dtype="float32"):
        super().__init__(dtype=dtype)
        self.stem = ConvBNLayer(3, 64, 7, stride=2, act="relu", dtype=dtype)
        self.pool = nn.MaxPool2D(3, 2, padding=1)
        chans = [128, 256, 512, 1024]
        blocks = []
        prev = 64
        for stage, (ch, depth) in enumerate(zip(chans, depths)):
            for i in range(depth):
                stride = 2 if i == 0 and stage > 0 else 1
                blocks.append(SEResNeXtBlock(prev, ch, stride=stride,
                                             dtype=dtype))
                prev = ch * 2
        self.blocks = nn.LayerList(blocks)
        self.global_pool = nn.Pool2D(pool_type="avg", global_pooling=True)
        self.fc = nn.Linear(prev, num_classes, dtype=dtype)

    def forward(self, x):
        # NCHW only (the SE gate's reshape assumes channel-first);
        # NHWC support lives on the ResNet family
        x = self.pool(self.stem(x))
        for b in self.blocks:
            x = b(x)
        x = self.global_pool(x)
        return self.fc(x.reshape(x.shape[0], -1))
