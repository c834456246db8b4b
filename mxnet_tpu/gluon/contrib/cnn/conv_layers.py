"""Deformable convolution block.
reference: python/mxnet/gluon/contrib/cnn/conv_layers.py
(DeformableConvolution): an ordinary conv predicts per-tap sampling
offsets, which drive `_contrib_DeformableConvolution` over the input.
"""
from __future__ import annotations

from ...block import HybridBlock
from ... import nn


class DeformableConvolution(HybridBlock):
    """Deformable conv v1 (Dai et al. 2017). The offset branch is a plain
    Conv2D producing 2*deformable_groups*kh*kw channels ([y, x] per tap),
    zero-initialized so training starts as a regular convolution —
    the reference's initialization convention."""

    def __init__(self, channels, kernel_size=(1, 1), strides=(1, 1),
                 padding=(0, 0), dilation=(1, 1), groups=1,
                 num_deformable_group=1, layout="NCHW", use_bias=True,
                 in_channels=0, activation=None, weight_initializer=None,
                 bias_initializer="zeros",
                 offset_weight_initializer="zeros",
                 offset_bias_initializer="zeros", offset_use_bias=True,
                 **kwargs):
        super().__init__(**kwargs)
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size, kernel_size)
        if isinstance(strides, int):
            strides = (strides, strides)
        if isinstance(padding, int):
            padding = (padding, padding)
        if isinstance(dilation, int):
            dilation = (dilation, dilation)
        assert layout == "NCHW", \
            "DeformableConvolution supports layout='NCHW' only"
        self._kwargs = {
            "kernel": kernel_size, "stride": strides, "pad": padding,
            "dilate": dilation, "num_filter": channels,
            "num_group": groups,
            "num_deformable_group": num_deformable_group,
            "no_bias": not use_bias}
        offset_channels = 2 * num_deformable_group * \
            kernel_size[0] * kernel_size[1]
        with self.name_scope():
            self.offset = nn.Conv2D(
                offset_channels, kernel_size=kernel_size, strides=strides,
                padding=padding, dilation=dilation, use_bias=offset_use_bias,
                weight_initializer=offset_weight_initializer,
                bias_initializer=offset_bias_initializer,
                in_channels=in_channels, prefix="offset_")
            kh, kw = kernel_size
            self.weight = self.params.get(
                "weight", shape=(channels, in_channels // groups, kh, kw),
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(channels,), init=bias_initializer,
                    allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = nn.Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def _shape_from_input(self, x, *args):
        groups = self._kwargs["num_group"]
        k = self._kwargs["kernel"]
        self.weight.shape = (self._kwargs["num_filter"],
                             x.shape[1] // groups) + k

    def hybrid_forward(self, F, x, weight, bias=None):
        offset = self.offset(x)
        if bias is None:
            out = F.contrib.DeformableConvolution(x, offset, weight,
                                                  **self._kwargs)
        else:
            out = F.contrib.DeformableConvolution(x, offset, weight, bias,
                                                  **self._kwargs)
        if self.act is not None:
            out = self.act(out)
        return out

