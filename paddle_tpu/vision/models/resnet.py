"""ResNet family (reference: python/paddle/vision/models/resnet.py, the
ResNet50 of paddle.vision.models + paddle.Model).

Same architecture/API as the reference; compute lowers to
lax.conv_general_dilated which XLA tiles onto the MXU as implicit GEMM. For
peak TPU throughput run in bfloat16 (`model.bfloat16()`); BatchNorm stats
accumulate in fp32 regardless (nn/functional/norm.py).
"""

from __future__ import annotations

from ... import nn

__all__ = ["ResNet", "resnet18", "resnet34", "resnet50", "resnet101",
           "resnet152", "resnext50_32x4d", "resnext50_64x4d",
           "resnext101_32x4d", "resnext101_64x4d", "resnext152_32x4d",
           "resnext152_64x4d", "wide_resnet50_2", "wide_resnet101_2"]


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, norm_layer=None):
        super().__init__()
        norm_layer = norm_layer or nn.BatchNorm2D
        assert groups == 1 and base_width == 64, "BasicBlock: groups/base_width fixed"
        self.conv1 = nn.Conv2D(inplanes, planes, 3, padding=1, stride=stride,
                               bias_attr=False)
        self.bn1 = norm_layer(planes)
        self.relu = nn.ReLU()
        self.conv2 = nn.Conv2D(planes, planes, 3, padding=1, bias_attr=False)
        self.bn2 = norm_layer(planes)
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, norm_layer=None):
        super().__init__()
        norm_layer = norm_layer or nn.BatchNorm2D
        width = int(planes * (base_width / 64.0)) * groups
        self.conv1 = nn.Conv2D(inplanes, width, 1, bias_attr=False)
        self.bn1 = norm_layer(width)
        self.conv2 = nn.Conv2D(width, width, 3, padding=dilation, stride=stride,
                               groups=groups, dilation=dilation, bias_attr=False)
        self.bn2 = norm_layer(width)
        self.conv3 = nn.Conv2D(width, planes * self.expansion, 1, bias_attr=False)
        self.bn3 = norm_layer(planes * self.expansion)
        self.relu = nn.ReLU()
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class ResNet(nn.Layer):
    def __init__(self, block, depth=50, width=64, num_classes=1000,
                 with_pool=True, groups=1):
        super().__init__()
        layer_cfg = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
                     101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}
        layers = layer_cfg[depth]
        self.groups = groups
        self.base_width = width
        self.num_classes = num_classes
        self.with_pool = with_pool
        self._norm_layer = nn.BatchNorm2D
        self.inplanes = 64
        self.dilation = 1
        self.conv1 = nn.Conv2D(3, self.inplanes, kernel_size=7, stride=2,
                               padding=3, bias_attr=False)
        self.bn1 = self._norm_layer(self.inplanes)
        self.relu = nn.ReLU()
        self.maxpool = nn.MaxPool2D(kernel_size=3, stride=2, padding=1)
        self.layer1 = self._make_layer(block, 64, layers[0])
        self.layer2 = self._make_layer(block, 128, layers[1], stride=2)
        self.layer3 = self._make_layer(block, 256, layers[2], stride=2)
        self.layer4 = self._make_layer(block, 512, layers[3], stride=2)
        if with_pool:
            self.avgpool = nn.AdaptiveAvgPool2D((1, 1))
        if num_classes > 0:
            self.fc = nn.Linear(512 * block.expansion, num_classes)

    def _make_layer(self, block, planes, blocks, stride=1, dilate=False):
        norm_layer = self._norm_layer
        downsample = None
        previous_dilation = self.dilation
        if dilate:
            self.dilation *= stride
            stride = 1
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = nn.Sequential(
                nn.Conv2D(self.inplanes, planes * block.expansion, 1,
                          stride=stride, bias_attr=False),
                norm_layer(planes * block.expansion))
        layers = [block(self.inplanes, planes, stride, downsample, self.groups,
                        self.base_width, previous_dilation, norm_layer)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes, groups=self.groups,
                                base_width=self.base_width,
                                norm_layer=norm_layer))
        return nn.Sequential(*layers)

    def forward(self, x):
        x = self.relu(self.bn1(self.conv1(x)))
        x = self.maxpool(x)
        x = self.layer1(x)
        x = self.layer2(x)
        x = self.layer3(x)
        x = self.layer4(x)
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            from ... import tensor as T
            x = T.flatten(x, 1)
            x = self.fc(x)
        return x


def _resnet(block, depth, **kwargs):
    return ResNet(block, depth, **kwargs)


def resnet18(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BasicBlock, 18, **kwargs),
                           "resnet18", pretrained)


def resnet34(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BasicBlock, 34, **kwargs),
                           "resnet34", pretrained)


def resnet50(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 50, **kwargs),
                           "resnet50", pretrained)


def resnet101(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 101, **kwargs),
                           "resnet101", pretrained)


def resnet152(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 152, **kwargs),
                           "resnet152", pretrained)


def resnext50_32x4d(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 50, groups=32, width=4, **kwargs), "resnext50_32x4d", pretrained)


def resnext50_64x4d(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 50, groups=64, width=4, **kwargs), "resnext50_64x4d", pretrained)


def resnext101_32x4d(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 101, groups=32, width=4, **kwargs), "resnext101_32x4d", pretrained)


def resnext101_64x4d(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 101, groups=64, width=4, **kwargs), "resnext101_64x4d", pretrained)


def resnext152_32x4d(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 152, groups=32, width=4, **kwargs), "resnext152_32x4d", pretrained)


def resnext152_64x4d(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 152, groups=64, width=4, **kwargs), "resnext152_64x4d", pretrained)


def wide_resnet50_2(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 50, width=128, **kwargs), "wide_resnet50_2", pretrained)


def wide_resnet101_2(pretrained=False, **kwargs):
    from ._zoo import load_pretrained
    return load_pretrained(_resnet(BottleneckBlock, 101, width=128, **kwargs), "wide_resnet101_2", pretrained)
