"""The plain float32 references against the program's flax models, at tiny
sizes on the CPU, on seeded weights and perturbed batch-norm statistics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import resnet, vit


def _variables(model, size, seed):
    v = jax.jit(lambda: model.init(jax.random.key(seed),
                                   jnp.zeros((1, size, size, 3)),
                                   train=False))()
    v = harness.plain_variables(v)
    if "batch_stats" in v:      # fresh statistics (0, 1) would hide errors
        rng = np.random.default_rng(seed)
        v["batch_stats"] = jax.tree_util.tree_map(
            lambda a: a + 0.1 * np.abs(rng.standard_normal(a.shape)
                                       ).astype(np.float32),
            v["batch_stats"])
    return v


@pytest.mark.parametrize("name,ref,config,size", [
    ("resnet18-cifar", resnet, None, 32),       # basic blocks, small stem
    ("resnet50", resnet, None, 64),             # bottlenecks, 7x7 stem
    ("vit-tiny", vit, {"num_heads": 4}, 32),
    ("vit-s16", vit, None, 64),                 # heads = hidden / 64
])
def test_reference_agrees_with_the_programs_model(name, ref, config, size):
    from tpuic.models import create_model
    x = np.random.default_rng(3).standard_normal(
        (4, size, size, 3)).astype(np.float32)
    m32 = create_model(name, 10, dtype="float32")
    v = _variables(m32, size, seed=1)
    want = ref.forward(v, x, config)
    assert want.shape == (4, 10) and want.dtype == jnp.float32
    # float32 against float32: the same mathematics to rounding
    assert harness.centred_error(m32.apply(v, x, train=False), want) < 1e-4
    # bfloat16 compute stays inside a tolerance that wrong mathematics
    # (here: batch norm applied with the wrong epsilon) does not
    m16 = create_model(name, 10, dtype="bfloat16")
    assert harness.centred_error(m16.apply(v, x, train=False), want) < 0.08


def test_centred_error_ignores_a_shift_and_sees_a_scale():
    w = np.random.default_rng(0).standard_normal((3, 10))
    assert harness.centred_error(w + 5.0, w) < 1e-12
    assert harness.centred_error(1.5 * w, w) == pytest.approx(0.5)
