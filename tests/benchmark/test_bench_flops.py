"""Required FLOPs from shapes, pinned to the published counts."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.flops import (forward_macs, model_forward_macs_per_image,
                             train_step_flops)


@pytest.mark.parametrize("model,published_gmacs", [
    ("resnet50", 4.09),     # torchvision resnet50 at 224 px
    ("vit-b16", 17.6),      # timm vit_base_patch16_224 (17.58)
])
def test_forward_macs_match_the_published_count(model, published_gmacs):
    from tpuic.models import create_model
    m = create_model(model, 1000, dtype="bfloat16")
    gmacs = model_forward_macs_per_image(m, 224) / 1e9
    assert gmacs == pytest.approx(published_gmacs, rel=0.03)


def test_dot_conv_and_scan_are_counted_and_nothing_else():
    a = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert forward_macs(lambda x, y: jnp.tanh(x @ y) + 1.0, a, b) == 4 * 8 * 16

    x = jax.ShapeDtypeStruct((2, 10, 10, 3), jnp.float32)
    k = jax.ShapeDtypeStruct((3, 3, 3, 5), jnp.float32)

    def conv(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert forward_macs(conv, x, k) == 2 * 10 * 10 * 5 * 3 * 3 * 3

    def scanned(x, y):
        return jax.lax.scan(lambda c, _: (jnp.tanh(c @ y @ y.T), None),
                            x, None, length=7)[0]
    assert forward_macs(scanned, a, b) == 7 * (4 * 8 * 16 + 4 * 16 * 8)


def test_recomputation_is_not_required_work():
    a = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    plain = forward_macs(lambda x, y: x @ y, a, b)
    assert forward_macs(jax.checkpoint(lambda x, y: x @ y), a, b) == plain
    assert train_step_flops(1e9, 128) == 3 * 2 * 1e9 * 128
