"""Compile-only rehearsal of every Pallas entry point, and of the resident
loader's per-batch program, for a described v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so Mosaic
refusals interpret mode cannot see — a strided vector slice, a block that
overflows VMEM — fail here, on the CPU, at no chip time. Nothing runs: a
pass says the kernel compiles at this shape, not that it is right or fast
(tests/test_kernels.py pins numerics in interpret mode; chip_smoke.py runs
the kernels on the chip).

Shapes are the main path's published widths: ViT-B/16 attention at 224 px
(N=197) and 768 px (N=2305), latent attention's core at the routed cell's
(batch 32, N=196, 32 heads of 128 + 64 / 128), the banded flash kernels at
the long-sequence cell's (batch 4, N=4096, 32 heads over 4 of 128, a window
of 1,024 and none), the 1000-class loss at batch 128, ResNet-50
leaves and layers; and the routed sum at both routed cells' shapes, which
has no kernel of its own but whose optimized program scatters no rows
where its token-side sums are gathers. The whole-step compile (~36 s) is
not tier-1; see scripts/chip_compile_rehearsal.py.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpuic.kernels import (causal_attention, flash_attention,
                           fused_conv_bn_relu, fused_weighted_cross_entropy,
                           lamb_leaf_update, lars_leaf_update)


@pytest.fixture(scope="module")
def chips():
    """The four described chips of one v5e host, persistent cache off: a
    compile for a described chip is written to the cache but cannot be read
    back without one, and the next run would warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(chips):
    """One described v5e chip's sharding."""
    return SingleDeviceSharding(chips[0])


def _compile(fn, chip, *shapes):
    """Lower ``fn`` at ``(shape, dtype)`` args placed on the described chip
    and compile; returns the number of Mosaic kernels in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", [(64, 197, 12, 64), (8, 2305, 12, 64)],
                         ids=["vitb16_224", "vitb16_768"])
def test_flash_attention(chip, shape, grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, None, None, False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(F32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    n = _compile(fn, chip, *[(shape, BF16)] * 3)
    assert n >= (2 if grad else 1)


# Latent attention's core at the routed cell's shapes (batch 32, 196 tokens,
# 32 heads of 128 + 64 / 128; a head's key beside its value as kv_b writes
# them), and the same kernel without rotary pieces at the looped stack's
# (16 heads of 128, keys and values apart).
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("heads,rope,packed", [(32, 64, True),
                                               (16, 0, False)],
                         ids=["kanana_30b_packed_kv", "ouro_no_rotary"])
def test_causal_attention(chip, heads, rope, packed, grad):
    def fwd(q_nope, *rest):
        keys = dict(kv=rest[0]) if packed else dict(k_nope=rest[0],
                                                    v=rest[1])
        rotary = dict(q_rope=rest[-2], k_rope=rest[-1]) if rope else {}
        return causal_attention.causal_attention(
            q_nope, **keys, **rotary, interpret=False)

    def loss(*pieces):
        return jnp.sum(fwd(*pieces).astype(F32))

    def piece(*dims):
        return ((32, 196) + dims, BF16)
    shapes = [piece(heads, 128)] + (
        [piece(heads, 256)] if packed else [piece(heads, 128)] * 2) + (
        [piece(heads, rope), piece(rope)] if rope else [])
    fn = jax.grad(loss, argnums=tuple(range(len(shapes)))) if grad else fwd
    # one kernel forward; a gradient holds it (the output and the rows'
    # log-sum-exp are the residuals) and ONE backward kernel
    assert _compile(fn, chip, *shapes) == (2 if grad else 1)


# The routed sum forward and backward at the two routed cells' shapes (the
# long-sequence cell's 16,384 tokens choosing 8 of 64 experts, 8 held, width
# 2,304, through 32,768 rows; the routed cell's 6,272 tokens choosing 6 of
# 128, 8 held, width 2,048, through 4,736): where its token-side sums are
# gathers the optimized program scatters no rows, in either branch of its
# cond, and where they are scatter-adds it does.
@pytest.mark.parametrize("tokens,width,hidden,top_k,experts,gathers", [
    (16384, 2304, 896, 8, 64, True), (6272, 2048, 768, 6, 128, False)],
    ids=["mellum_12b", "kanana_30b"])
def test_the_routed_sum_scatters_no_rows(chip, tokens, width, hidden, top_k,
                                         experts, gathers):
    import re
    from tpuic.models import kanana

    def loss(x, weights, gate_up, down, chosen):
        y = kanana.routed_sum(x, chosen, weights, gate_up, down, 0,
                              experts)[0]
        return jnp.sum(y * y)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((tokens, width), BF16), ((tokens, top_k), F32),
        ((8, width, 2 * hidden), BF16), ((8, hidden, width), BF16),
        ((tokens, top_k), I32))]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        *args).compile().as_text()
    assert "ragged" in text
    scatters = re.findall(r"= \w+\[\d+,\d+\]\S* scatter\(", text)
    assert (scatters == []) == gathers


# The banded flash kernels at the long-sequence cell's shapes (batch 4,
# 4,096 tokens, 32 query heads over 4 key-value heads of 128): a sliding
# layer's window and a full layer's causal mask, through the one function.
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("window", [1024, None], ids=["sliding", "full"])
def test_banded_flash_attention(chip, window, grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, None, None, False, None, None, True,
                               window)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(F32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    shapes = [((4, 4096, 32, 128), BF16)] + [((4, 4096, 4, 128), BF16)] * 2
    # forward; a gradient holds it and the dq and dk/dv kernels
    assert _compile(fn, chip, *shapes) == (3 if grad else 1)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_fused_cross_entropy(chip, grad):
    def fwd(logits, labels):
        return fused_weighted_cross_entropy(logits, labels, None, None,
                                            0.0, 128, False)

    fn = jax.grad(fwd) if grad else fwd
    assert _compile(fn, chip, ((128, 1000), F32), ((128,), I32)) >= 1


@pytest.mark.parametrize("leaf", [(3, 3, 512, 512), (64,)],
                         ids=["conv3x3_512", "bn_64"])
@pytest.mark.parametrize("opt", ["lars", "lamb"])
def test_fused_optimizer_leaf_update(chip, opt, leaf):
    if opt == "lars":
        def fn(w, g, m):
            return lars_leaf_update(w, g, m, lr=0.1, weight_decay=1e-4,
                                    trust_coefficient=1e-3, momentum=0.9,
                                    impl="pallas", interpret=False)
        shapes = [(leaf, F32)] * 3
    else:
        def fn(w, g, m, v, count):
            return lamb_leaf_update(w, g, m, v, count, lr=1e-3, b1=0.9,
                                    b2=0.999, eps=1e-6, weight_decay=1e-2,
                                    impl="pallas", interpret=False)
        shapes = [(leaf, F32)] * 4 + [((), I32)]
    assert _compile(fn, chip, *shapes) == 1


# ResNet-50 @224 layers: layer1's 3x3, and layer2's first block where the
# stride sits (torchvision v1.5 puts it on the 3x3; the 1x1 projection
# shortcut is strided too).
@pytest.mark.parametrize("x,w,stride,pad", [
    ((8, 56, 56, 64), (3, 3, 64, 64), 1, 1),
    ((8, 56, 56, 128), (3, 3, 128, 128), 2, 1),
    ((8, 56, 56, 256), (1, 1, 256, 512), 2, 0),
], ids=["layer1_3x3_s1", "layer2_3x3_s2", "layer2_proj_1x1_s2"])
def test_fused_conv_bn_relu(chip, x, w, stride, pad):
    def fn(xv, wv, scale, bias):
        return fused_conv_bn_relu(xv, wv, scale, bias, strides=stride,
                                  padding=pad, interpret=False)

    cout = w[-1]
    assert _compile(fn, chip, (x, BF16), (w, BF16), ((cout,), F32),
                    ((cout,), F32)) == 1


def test_fused_conv_bn_relu_refuses_the_224px_stem(chip):
    """The 7x7/2 ImageNet stem (Cin=3 pads to 128 lanes) cannot hold one
    image's blocks in VMEM: the wrapper says so by shape instead of
    handing Mosaic a program it is certain to refuse (models/resnet.py
    keeps that layer unfused)."""
    def fn(xv, wv, scale, bias):
        return fused_conv_bn_relu(xv, wv, scale, bias, strides=2,
                                  padding=3, interpret=False)

    with pytest.raises(ValueError, match="MiB of VMEM"):
        _compile(fn, chip, ((8, 224, 224, 3), BF16), ((7, 7, 3, 64), BF16),
                 ((64,), F32), ((64,), F32))


def _resident_prep_facts(chips, size, rows, batch, meshed):
    """check_resident_prep for one described chip, or for the four under a
    ``data`` mesh with ``batch`` the global batch."""
    import numpy as np
    from jax.sharding import Mesh

    from tpuic.data.device_prep import check_resident_prep
    mesh = Mesh(np.array(chips), ("data",)) if meshed else None
    return check_resident_prep(size, rows=rows, batch=batch, mesh=mesh,
                               device=chips[0])


@pytest.mark.parametrize("size,rows,batch,meshed", [
    (224, 5120, 128, False), (224, 5120, 64, False),
    (224, 20480, 512, True), (299, 8192, 128, False)],
    ids=["resnet50_cell", "vit_b16_cell", "dp4_cell", "px299_two_pieces"])
def test_resident_prep_reads_rows_in_place(chips, size, rows, batch, meshed):
    """The resident loader's per-batch program at the benchmark cells'
    shapes (and at 299 px, where a row is gathered as two pieces): no
    temporary of the compiled program is corpus-sized. Held as [N,S,S,3]
    this read 881 MB against a corpus of 771 MB: the copy of the whole
    corpus that ran in every step until PR 26."""
    facts = _resident_prep_facts(chips, size, rows, batch, meshed)
    # A few float copies of one chip's batch, whatever the corpus.
    per_chip = batch // (len(chips) if meshed else 1)
    assert facts["temp_bytes"] <= 4 * per_chip * size * size * 3 * 4


# What the compiler counted for the parent of PR 30 (four rot90 variants
# of the batch in float32, selected among): the record the composed
# geometry is held against, in bytes.
@pytest.mark.parametrize("rows,batch,meshed,was,share", [
    (5120, 128, False, 2228.7e6, 0.40), (5120, 64, False, 1546.5e6, 0.40),
    (20480, 512, True, 2228.7e6, 0.40), (256, 32, False, 1104.5e6, 0.60)],
    ids=["resnet50_cell", "vit_b16_cell", "dp4_cell", "ouro_cell"])
def test_resident_prep_moves_the_batch_as_bytes(chips, rows, batch, meshed,
                                                was, share):
    """The augmentation's geometry at the four cells' shapes: whatever is
    reversed is the uint8 batch (check_resident_prep refuses a float32
    reversal of the batch's size), and the compiler's count of the
    program's traffic stays at most this share of what it was when every
    image was rotated four ways in float32."""
    facts = _resident_prep_facts(chips, 224, rows, batch, meshed)
    assert facts["f32_reversals"] == []
    assert facts["bytes_accessed"] <= share * was, facts
