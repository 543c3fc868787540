"""ResNet family (18/34/50/101) as Flax modules.

Backbone capability parity with the reference's torchvision selections
(nn/classifier.py:11-15 offers resnet50/resnet101 pretrained; BASELINE.md adds
resnet18 for the CIFAR-10 config). Torchvision's exact architecture is
reproduced — 7x7/stride-2 stem, maxpool, 4 stages of Basic/Bottleneck blocks,
global average pool — so its pretrained checkpoints can be converted 1:1
(tpuic/checkpoint/torch_convert.py). Layout is NHWC (TPU-native; torch is
NCHW), compute dtype is configurable bfloat16 for the MXU.

A ``small_stem`` variant (3x3 stride-1 stem, no maxpool) is provided for
32x32 CIFAR inputs, where the ImageNet stem would destroy resolution.

``space_to_depth`` stem (the public MLPerf ResNet TPU optimization): the
7x7/stride-2 conv on [H, W, 3] is algebraically identical to a 4x4/stride-1
conv on the 2x2 space-to-depth transform [H/2, W/2, 12] with the 7x7 kernel
zero-padded to 8x8 and re-indexed (``s2d_stem_kernel``). C=3 feeds the
128-lane MXU at ~2% utilization; C=12 is 4x better and the stride-2 gather
disappears. Same math, better layout — exactness is pinned in
tests/test_models.py.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpuic.models.layers import batch_norm, conv1x1, conv3x3


def _fused_ready(mod: nn.Module, train: bool) -> bool:
    """The fused-inference branch applies only when (a) the flag is on,
    (b) this is an inference call (training BN needs batch statistics
    the per-image kernel cannot see), and (c) the variables already
    exist — init() must run the unfused branch so the parameter
    structure (and therefore every checkpoint) is identical either way."""
    return (mod.fused_inference and not train
            and mod.has_variable("params", "conv1"))


def _fused_cbr(mod: nn.Module, x, conv: str, bn: str, *, strides=1,
               padding=0, relu=True):
    """One fused conv+BN+ReLU call reading the UNFUSED branch's variables
    (kernels/conv_bn_relu.py) — same params, same running stats, one
    VMEM pass instead of conv-out/bn-out/relu-out HBM roundtrips."""
    from tpuic.kernels import fused_conv_bn_from_flax
    v = mod.variables
    return fused_conv_bn_from_flax(
        x, v["params"][conv]["kernel"], v["params"][bn],
        v["batch_stats"][bn], strides=strides, padding=padding, relu=relu,
        eps=mod.bn_eps)


class BasicBlock(nn.Module):
    features: int
    strides: int = 1
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    bn_f32_stats: bool = True
    fused_inference: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool) -> jnp.ndarray:
        if _fused_ready(self, train):
            y = _fused_cbr(self, x, "conv1", "bn1", strides=self.strides,
                           padding=1)
            y = _fused_cbr(self, y, "conv2", "bn2", padding=1, relu=False)
            residual = x
            if "downsample_conv" in self.variables["params"]:
                residual = _fused_cbr(self, x, "downsample_conv",
                                      "downsample_bn",
                                      strides=self.strides, relu=False)
            return nn.relu(y + residual)
        bn = partial(batch_norm, train, momentum=self.bn_momentum,
                     eps=self.bn_eps, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     f32_stats=self.bn_f32_stats)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        residual = x
        y = conv3x3(self.features, self.strides, **kw, name="conv1")(x)
        y = bn(name="bn1")(y)
        y = nn.relu(y)
        y = conv3x3(self.features, **kw, name="conv2")(y)
        y = bn(name="bn2")(y)
        if residual.shape != y.shape:
            residual = conv1x1(self.features, self.strides, **kw,
                               name="downsample_conv")(x)
            residual = bn(name="downsample_bn")(residual)
        return nn.relu(y + residual)


class Bottleneck(nn.Module):
    features: int  # bottleneck width; block output is 4*features
    strides: int = 1
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    bn_f32_stats: bool = True
    fused_inference: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool) -> jnp.ndarray:
        if _fused_ready(self, train):
            y = _fused_cbr(self, x, "conv1", "bn1")
            # torchvision places the stride on the 3x3 (v1.5 ResNet).
            y = _fused_cbr(self, y, "conv2", "bn2", strides=self.strides,
                           padding=1)
            y = _fused_cbr(self, y, "conv3", "bn3", relu=False)
            residual = x
            if "downsample_conv" in self.variables["params"]:
                residual = _fused_cbr(self, x, "downsample_conv",
                                      "downsample_bn",
                                      strides=self.strides, relu=False)
            return nn.relu(y + residual)
        bn = partial(batch_norm, train, momentum=self.bn_momentum,
                     eps=self.bn_eps, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     f32_stats=self.bn_f32_stats)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        out_features = self.features * 4
        residual = x
        y = conv1x1(self.features, **kw, name="conv1")(x)
        y = nn.relu(bn(name="bn1")(y))
        # torchvision places the stride on the 3x3 (v1.5 ResNet).
        y = conv3x3(self.features, self.strides, **kw, name="conv2")(y)
        y = nn.relu(bn(name="bn2")(y))
        y = conv1x1(out_features, **kw, name="conv3")(y)
        y = bn(name="bn3")(y)
        if residual.shape != y.shape:
            residual = conv1x1(out_features, self.strides, **kw,
                               name="downsample_conv")(x)
            residual = bn(name="downsample_bn")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """Returns pooled features [B, F]; the classifier head is separate."""

    stage_sizes: Sequence[int]
    block: type
    num_filters: int = 64
    small_stem: bool = False
    space_to_depth: bool = False
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    bn_f32_stats: bool = True
    # Inference-only Pallas fused conv+BN+ReLU (kernels/conv_bn_relu.py):
    # identical parameter structure (init always runs the unfused branch),
    # so the flag can be flipped on any existing checkpoint.
    fused_inference: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = x.astype(self.dtype)
        # The fused kernel holds one whole image per grid step, and the
        # ImageNet stem's input (Cin=3, or 12 after space-to-depth, padded
        # to 128 lanes) does not fit VMEM at 224 px: only the small stem
        # fuses; the 7x7 stem always runs the unfused graph.
        fused = _fused_ready(self, train) and self.small_stem
        # jax.named_scope tags ('stem'/'gap') thread the structural
        # phases flax's module path does not name into the HLO op
        # metadata — the device-time waterfall (telemetry/profile.py)
        # rolls layers up from exactly these paths; the blocks below are
        # already scoped by their flax module names (layerN_i).
        with jax.named_scope("stem"):
            if fused:
                x = _fused_cbr(self, x, "conv1", "bn1", padding=1)
            elif self.small_stem:
                x = nn.Conv(self.num_filters, (3, 3), padding=1,
                            use_bias=False, **kw, name="conv1")(x)
            elif self.space_to_depth:
                b, h, w, c = x.shape
                if h % 2 or w % 2:
                    raise ValueError(
                        f"space_to_depth stem needs even H/W, got {(h, w)}")
                x = x.reshape(b, h // 2, 2, w // 2, 2, c)
                x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2,
                                                          4 * c)
                # Taps of output row oi cover original rows 2oi-3..2oi+3;
                # with the kernel zero-padded to 8 the window is
                # 2(oi-2)..2oi+3 — four s2d rows, hence 4x4 stride-1 with
                # (2, 1) padding.
                x = nn.Conv(self.num_filters, (4, 4), strides=(1, 1),
                            padding=((2, 1), (2, 1)), use_bias=False, **kw,
                            name="conv1")(x)
            else:
                x = nn.Conv(self.num_filters, (7, 7), strides=(2, 2),
                            padding=3, use_bias=False, **kw, name="conv1")(x)
            if not fused:  # the fused stem already applied bn1 + relu
                x = batch_norm(train, momentum=self.bn_momentum,
                               eps=self.bn_eps,
                               f32_stats=self.bn_f32_stats, **kw,
                               name="bn1")(x)
                x = nn.relu(x)
            if not self.small_stem:
                x = nn.max_pool(x, (3, 3), strides=(2, 2),
                                padding=((1, 1), (1, 1)))
        for stage, n_blocks in enumerate(self.stage_sizes):
            for i in range(n_blocks):
                strides = 2 if stage > 0 and i == 0 else 1
                x = self.block(self.num_filters * 2 ** stage, strides,
                               self.bn_momentum, self.bn_eps, self.dtype,
                               self.param_dtype, self.bn_f32_stats,
                               fused_inference=self.fused_inference,
                               name=f"layer{stage + 1}_{i}")(x, train)
        with jax.named_scope("gap"):
            x = jnp.mean(x, axis=(1, 2))  # global average pool
        return x.astype(jnp.float32)


def s2d_stem_kernel(w77: jnp.ndarray) -> jnp.ndarray:
    """[7,7,Cin,F] stem kernel -> its space-to-depth equivalent
    [4,4,4*Cin,F]: zero-pad to 8x8 with the extra row/col at the LEADING
    edge (the conv's effective window starts one original pixel earlier),
    then fold each 2x2 tap block into channels in (di, dj, channel) order —
    matching the activation transform in ResNet.__call__."""
    k, _, cin, f = w77.shape
    assert k == 7, w77.shape
    w88 = jnp.pad(w77, ((1, 0), (1, 0), (0, 0), (0, 0)))
    w = w88.reshape(4, 2, 4, 2, cin, f)          # (pi, di, qi, dj, c, f)
    w = w.transpose(0, 2, 1, 3, 4, 5)            # (pi, qi, di, dj, c, f)
    return w.reshape(4, 4, 4 * cin, f)


def resnet18(**kw) -> ResNet:
    return ResNet(stage_sizes=(2, 2, 2, 2), block=BasicBlock, **kw)


def resnet34(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BasicBlock, **kw)


def resnet50(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=Bottleneck, **kw)


def resnet101(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 23, 3), block=Bottleneck, **kw)


def resnet152(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 8, 36, 3), block=Bottleneck, **kw)
