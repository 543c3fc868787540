"""Fused conv + folded-BN affine + ReLU as a Pallas TPU kernel.

The ResNet inference hot path is ``conv -> batch_norm -> relu`` repeated
~50 times.  At inference BN is a pure per-channel affine (running stats
are constants), yet the unfused graph writes the conv output to HBM,
reads it back for the scale/shift, writes again, reads again for the
ReLU — the elementwise/copy traffic the roofline waterfall
(telemetry/profile.py) books against the ``elementwise``/``copy``
classes.  This kernel keeps the whole block in VMEM:

- **conv as tap matmuls**: a KxK conv over an NHWC block is the sum over
  the K*K taps of ``[H_out*W_out, Cin] @ [Cin, Cout]`` matmuls — each
  tap feeds the 128x128 MXU as a plain GEMM (the same re-layout idea as
  the space-to-depth stem, models/resnet.py), accumulated in float32 in
  VMEM.
- **BN folded to an affine epilogue**: ``scale = gamma * rsqrt(var+eps)``
  and ``bias = beta - mean * scale`` are precomputed (``fold_bn``); the
  kernel applies ``y * scale + bias`` and the optional ReLU on the
  accumulator **before** the single output write.  One HBM write per
  block instead of conv-out + bn-out + relu-out.

Grid: one batch element per grid step — the weights and the affine stay
resident in VMEM across the grid, and per-image activations for the
ResNet stage sizes (<= 56x56x256 at 224px, <= 32x32x64 on CIFAR) fit.  The batch dim is embarrassingly parallel, so under a
sharded jit GSPMD keeps the kernel batch-parallel like every other
per-sample Pallas call here (cross_entropy.py's discipline).

**Inference only**: training BN needs the *batch* statistics of the conv
output (a cross-batch reduction this per-image kernel cannot see), so
the train path keeps the unfused reference graph; the flag that wires
this kernel into the model zoo (ModelConfig.fused_conv_bn) applies to
``train=False`` calls only, and numerics parity against the unfused
reference is pinned in tests/test_kernels.py (atol 1e-4 in float32 —
the tap-matmul accumulation order differs from XLA's conv).

On CPU (CI) the kernel runs in Pallas interpret mode like every other
kernel in this package; on TPU it compiles via Mosaic.  Mosaic takes only
unit-stride vector slices, so a strided conv is split into its stride
phases in XLA before the call and every tap reads a unit-stride window.
A layer whose per-image blocks exceed VMEM raises a ValueError by shape.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Padding = Union[int, Sequence[Tuple[int, int]]]


def fold_bn(gamma, beta, mean, var, eps: float = 1e-5):
    """BN running stats -> the per-channel affine the kernel applies.

    Matches ``nn.BatchNorm(use_running_average=True)`` exactly:
    ``y = (x - mean) * gamma * rsqrt(var + eps) + beta``.
    Returns float32 ``(scale, bias)`` rows of shape [Cout]."""
    scale = (jnp.asarray(gamma, jnp.float32)
             * jax.lax.rsqrt(jnp.asarray(var, jnp.float32) + eps))
    bias = jnp.asarray(beta, jnp.float32) - jnp.asarray(mean,
                                                        jnp.float32) * scale
    return scale, bias


def _norm_padding(padding: Padding) -> Tuple[Tuple[int, int],
                                             Tuple[int, int]]:
    if isinstance(padding, int):
        return ((padding, padding), (padding, padding))
    (pt, pb), (pl_, pr) = padding
    return ((int(pt), int(pb)), (int(pl_), int(pr)))


def _kernel(x_ref, w_ref, scale_ref, bias_ref, out_ref, *, kh: int, kw: int,
            sh: int, sw: int, ho: int, wo: int, relu: bool):
    """One batch element: accumulate the K*K tap matmuls in f32, apply
    the folded-BN affine + optional ReLU, write once."""
    cin = x_ref.shape[-1]
    cout = out_ref.shape[-1]
    # x_ref holds the sh*sw stride phases of the padded image (see
    # _fused): phase (pi, pj) is rows pi, pi+sh, ... / cols pj, pj+sw, ...
    # Only the phases some tap reads are there: min(k, s) along each axis.
    pw = min(kw, sw)
    phases = [x_ref[0, p] for p in range(x_ref.shape[1])]  # [Hq, Wq, Cin]
    acc = jnp.zeros((ho * wo, cout), jnp.float32)
    for ki in range(kh):
        for kj in range(kw):
            # Tap (ki, kj) reads rows ki, ki+sh, ...: a UNIT-stride window
            # at offset (ki // sh, kj // sw) of phase (ki % sh, kj % sw).
            oi, oj = ki // sh, kj // sw
            patch = jax.lax.slice(phases[(ki % sh) * pw + kj % sw],
                                  (oi, oj, 0), (oi + ho, oj + wo, cin))
            acc += jnp.dot(patch.reshape(ho * wo, cin), w_ref[ki, kj],
                           preferred_element_type=jnp.float32)
    y = acc * scale_ref[0] + bias_ref[0]
    if relu:
        y = jnp.maximum(y, 0.0)
    out_ref[0] = y.reshape(ho, wo, cout).astype(out_ref.dtype)


# Mosaic's scoped-VMEM limit on v5e.
_VMEM_LIMIT = 16 * 2 ** 20


def _tiled_bytes(shape, dtype) -> int:
    """Bytes of a VMEM block: the last dim pads to 128 lanes, the one
    before it to a sublane tile (8 rows of 32 bits)."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 8 * max(1, 4 // item)
    n = -(-rows // sub) * sub * -(-cols // 128) * 128 * item
    for d in lead:
        n *= d
    return n


@functools.partial(jax.jit, static_argnames=("strides", "padding", "relu",
                                             "interpret", "out_dtype"))
def _fused(x, w, scale, bias, strides, padding, relu, interpret, out_dtype):
    b, h, w_in, cin = x.shape
    kh, kw, wcin, cout = w.shape
    if wcin != cin:
        raise ValueError(f"kernel expects Cin={wcin}, input has {cin}")
    sh, sw = strides
    (pt, pb), (pl_, pr) = padding
    ho = (h + pt + pb - kh) // sh + 1
    wo = (w_in + pl_ + pr - kw) // sw + 1
    if ho < 1 or wo < 1:
        raise ValueError(f"empty output for input {x.shape}, kernel "
                         f"{w.shape}, strides {strides}, padding {padding}")
    # Mosaic takes only unit-stride vector slices, so the stride is taken
    # out here, in XLA: split the padded image into its sh*sw phases
    # [B, sh*sw, Hq, Wq, Cin] (for stride 1 a plain reshape) and let each
    # tap read a unit-stride window of one phase.  Rows/cols past the last
    # tap are cropped or zero-filled; no tap reads them.
    # A phase holds the deepest tap offset plus the output extent.
    hq, wq = (kh - 1) // sh + ho, (kw - 1) // sw + wo
    xp = jnp.pad(x, ((0, 0), (pt, max(0, hq * sh - h - pt)),
                     (pl_, max(0, wq * sw - w_in - pl_)), (0, 0)))
    xp = xp[:, :hq * sh, :wq * sw]
    xp = xp.reshape(b, hq, sh, wq, sw, cin).transpose(0, 2, 4, 1, 3, 5)
    ph, pw = min(kh, sh), min(kw, sw)    # a 1x1 stride-2 tap reads 1 phase
    xp = xp[:, :ph, :pw].reshape(b, ph * pw, hq, wq, cin)
    # One image's blocks are the least the kernel holds in VMEM (the
    # pipeline double-buffers what moves with the grid; the compiler's own
    # temporaries come on top).  Past the limit the compile is certain to
    # be refused: say so here, by shape.  The 224-px ImageNet stem is such
    # a layer (Cin=3 pads to 128 lanes); models/resnet.py keeps it unfused.
    need = (2 * _tiled_bytes(xp.shape[1:], x.dtype)
            + 2 * _tiled_bytes((ho, wo, cout), out_dtype)
            + _tiled_bytes(w.shape, w.dtype))
    if need > _VMEM_LIMIT:
        raise ValueError(
            f"fused_conv_bn_relu: input {x.shape}, kernel {w.shape}, "
            f"strides {strides} needs {need / 2 ** 20:.1f} MiB of VMEM for "
            f"one image's blocks, over the {_VMEM_LIMIT // 2 ** 20} MiB "
            "limit; run this layer unfused")
    # The grid walks the batch; weights + the affine rows use a constant
    # index map, so they stay VMEM-resident across all B steps.
    out = pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, sh=sh, sw=sw, ho=ho,
                          wo=wo, relu=relu),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, cout), out_dtype),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, ph * pw, hq, wq, cin),
                         lambda i: (i, 0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kh, kw, cin, cout), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, ho, wo, cout), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xp, w, scale[None, :], bias[None, :])
    return out


def fused_conv_bn_relu(x, w, scale, bias, *,
                       strides: Union[int, Tuple[int, int]] = 1,
                       padding: Padding = 0, relu: bool = True,
                       interpret: Optional[bool] = None,
                       out_dtype=None):
    """``relu(conv(x, w) * scale + bias)`` in one VMEM pass.

    x: [B, H, W, Cin] NHWC; w: [kh, kw, Cin, Cout] (flax nn.Conv layout);
    scale/bias: [Cout] — the folded BN affine from :func:`fold_bn` (pass
    ``scale=ones, bias=zeros`` for a bare conv+ReLU).  ``relu=False``
    stops before the activation (the residual-add case).  Accumulation
    is float32 regardless of input dtype; output dtype defaults to
    ``x.dtype``."""
    if interpret is None:
        from tpuic.kernels import default_interpret
        interpret = default_interpret()
    if isinstance(strides, int):
        strides = (strides, strides)
    with jax.named_scope("fused_conv_bn_relu"):
        return _fused(x, w, jnp.asarray(scale, jnp.float32),
                      jnp.asarray(bias, jnp.float32),
                      (int(strides[0]), int(strides[1])),
                      _norm_padding(padding), bool(relu), bool(interpret),
                      jnp.dtype(out_dtype or x.dtype))


def fused_conv_bn_from_flax(x, kernel, bn_params, bn_stats, *,
                            strides: Union[int, Tuple[int, int]] = 1,
                            padding: Padding = 0, relu: bool = True,
                            eps: float = 1e-5,
                            interpret: Optional[bool] = None):
    """Convenience wrapper over flax variable dicts: ``kernel`` is the
    nn.Conv ``kernel`` leaf, ``bn_params``/``bn_stats`` the matching
    nn.BatchNorm ``{'scale','bias'}`` / ``{'mean','var'}`` dicts — the
    exact trees the ResNet blocks read in their fused-inference branch
    (models/resnet.py)."""
    scale, bias = fold_bn(bn_params["scale"], bn_params["bias"],
                          bn_stats["mean"], bn_stats["var"], eps)
    return fused_conv_bn_relu(x, kernel, scale, bias, strides=strides,
                              padding=padding, relu=relu,
                              interpret=interpret)
