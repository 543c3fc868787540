"""ResNet (He et al., arXiv:1512.03385) as torchvision builds it (v1.5),
in plain float32 ``jax.numpy``.

Reads the parameter tree of the program's flax model: ``backbone.conv1``,
``backbone.bn1``, ``backbone.layer{stage}_{i}.{conv1..3, bn1..3,
downsample_conv, downsample_bn}`` and ``head.{fc0, fc1, fc2, out}``. The
stage sizes and the block kind are read off the tree, so ResNet-18 to -152
need no table here.

Departures from the published description, all the program's own:
- the head is the reference system's MLP (features -> 128 -> 64 -> 32 ->
  classes, ReLU between) in place of the paper's single fully-connected
  layer;
- eval mode: batch norm uses the running statistics; training-mode batch
  statistics are not part of this forward;
- a tree whose stem kernel is 3x3 is the CIFAR variant (stride 1, no max
  pool), which the CPU tests use at tiny sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5       # torchvision's default, which the program keeps


def _conv(x, kernel, stride: int, pad: int):
    return jax.lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, p, stats):
    inv = jax.lax.rsqrt(stats["var"].astype(jnp.float32) + BN_EPS)
    return ((x - stats["mean"].astype(jnp.float32)) * inv
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def _block(x, p, s, stride: int):
    """A bottleneck (1x1, 3x3 with the stride, 1x1) when the block has a
    third conv, else a basic block (3x3 with the stride, 3x3)."""
    bottleneck = "conv3" in p
    if bottleneck:
        y = jax.nn.relu(_bn(_conv(x, p["conv1"]["kernel"], 1, 0),
                            p["bn1"], s["bn1"]))
        y = jax.nn.relu(_bn(_conv(y, p["conv2"]["kernel"], stride, 1),
                            p["bn2"], s["bn2"]))
        y = _bn(_conv(y, p["conv3"]["kernel"], 1, 0), p["bn3"], s["bn3"])
    else:
        y = jax.nn.relu(_bn(_conv(x, p["conv1"]["kernel"], stride, 1),
                            p["bn1"], s["bn1"]))
        y = _bn(_conv(y, p["conv2"]["kernel"], 1, 1), p["bn2"], s["bn2"])
    if "downsample_conv" in p:
        x = _bn(_conv(x, p["downsample_conv"]["kernel"], stride, 0),
                p["downsample_bn"], s["downsample_bn"])
    return jax.nn.relu(y + x)


def _dense(x, p):
    return (jnp.dot(x, p["kernel"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
            + p["bias"].astype(jnp.float32))


def mlp_head(x, p):
    """The program's Classifier head: hidden layers ``fc0..`` with ReLU,
    then ``out``."""
    i = 0
    while f"fc{i}" in p:
        x = jax.nn.relu(_dense(x, p[f"fc{i}"]))
        i += 1
    return _dense(x, p["out"])


def forward(variables, images, config=None):
    """Logits [B, classes] for normalised float32 images [B, H, W, 3];
    ``config`` (the configuration file's content) is not needed: every
    size is read off the parameter tree."""
    del config
    p = variables["params"]["backbone"]
    s = variables["batch_stats"]["backbone"]
    x = jnp.asarray(images, jnp.float32)
    stem = p["conv1"]["kernel"]
    if stem.shape[0] == 7:
        x = jax.nn.relu(_bn(_conv(x, stem, 2, 3), p["bn1"], s["bn1"]))
        x = _max_pool_3x3_s2(x)
    else:
        x = jax.nn.relu(_bn(_conv(x, stem, 1, 1), p["bn1"], s["bn1"]))
    stage = 1
    while f"layer{stage}_0" in p:
        i = 0
        while f"layer{stage}_{i}" in p:
            name = f"layer{stage}_{i}"
            x = _block(x, p[name], s[name],
                       stride=2 if stage > 1 and i == 0 else 1)
            i += 1
        stage += 1
    x = jnp.mean(x, axis=(1, 2))
    return mlp_head(x, variables["params"]["head"])
