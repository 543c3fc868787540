#!/usr/bin/env python
"""Compile the main path's whole programs for a described v5e: no chip.

The TPU compiler installed with jax compiles for a chip that is described,
not attached, so what it would refuse on the chip (a kernel Mosaic does not
take, a program that does not fit 16 GB) it refuses here, at no chip time.
A pass is a compile, never a chip run: nothing executes and no time is
measured. tests/test_chip_compile.py keeps the kernels among the tier-1
tests; this script holds the whole-step programs, half a minute each:

- ResNet-50 bf16 @224, global batch 128, SGD train step on one chip, and
  the same global batch over a data=4 mesh (chip_smoke.py's two --chips 4
  legs);
- ViT-B/16 @224 batch 64 with flash attention, the fused loss and the fused
  LARS update (chip_smoke.py's kernels phase);
- the ResNet-50 eval forward at batch 8 with the fused conv+BN+ReLU kernel.

    JAX_PLATFORMS=cpu python scripts/chip_compile_rehearsal.py
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tpuic.config import MeshConfig, ModelConfig, OptimConfig
from tpuic.models import create_model_from_config
from tpuic.runtime.mesh import make_mesh
from tpuic.train.optimizer import make_optimizer
from tpuic.train.state import create_train_state
from tpuic.train.step import make_train_step


def _report(name, lowered):
    t = time.perf_counter()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: compiled in {time.perf_counter() - t:.0f} s; per device "
          f"temp {mem.temp_size_in_bytes / 2**30:.2f} GiB, args "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB; "
          f"{text.count('tpu_custom_call')} Mosaic kernel(s); collectives: "
          f"{sorted({c for c in ('all-reduce', 'all-gather', 'reduce-scatter') if c in text}) or 'none'}",
          flush=True)


def train_step(devices, mcfg, ocfg, global_batch, mesh_size):
    """Lower the jitted train step at shapes placed on described devices."""
    mesh = make_mesh(MeshConfig(data=mesh_size), devices=devices[:mesh_size])
    step_mesh = mesh if mesh_size > 1 else None
    repl = (NamedSharding(mesh, P()) if step_mesh is not None
            else SingleDeviceSharding(devices[0]))
    data = (NamedSharding(mesh, P("data")) if step_mesh is not None else repl)
    model = create_model_from_config(mcfg, mesh=mesh)
    shape = (global_batch, 224, 224, 3)
    state = jax.eval_shape(
        lambda: create_train_state(model, make_optimizer(ocfg),
                                   jax.random.key(0), shape))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl), state)
    batch = {"image": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=data),
             "label": jax.ShapeDtypeStruct((global_batch,), jnp.int32,
                                           sharding=data),
             "mask": jax.ShapeDtypeStruct((global_batch,), jnp.float32,
                                          sharding=data)}
    return make_train_step(ocfg, mcfg, step_mesh, donate=True).lower(state,
                                                                     batch)


def main() -> None:
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    sgd = OptimConfig(optimizer="sgd", class_weights=(), milestones=())
    resnet = ModelConfig(name="resnet50", num_classes=1000)
    _report("resnet50 b128 train step, one chip",
            train_step(devices, resnet, sgd, 128, 1))
    _report("resnet50 b128 train step, data=4 mesh (32/chip)",
            train_step(devices, resnet, sgd, 128, 4))
    # default_interpret()/default_opt_impl() read the CPU backend here, so
    # the kernels are steered to their chip form by hand.
    import tpuic.kernels as kernels
    import tpuic.kernels.optimizer_update as opt_update
    kernels.default_interpret = lambda: False
    opt_update.default_opt_impl = lambda: "pallas"
    _report("vit-b16 b64 train step: flash + fused CE + fused LARS",
            train_step(devices,
                       ModelConfig(name="vit-b16", num_classes=1000,
                                   attention="flash"),
                       OptimConfig(optimizer="lars", class_weights=(),
                                   milestones=(), fused_loss=True,
                                   fused_optimizer=True), 64, 1))
    fused = create_model_from_config(ModelConfig(
        name="resnet50", num_classes=1000, fused_conv_bn=True))
    one = SingleDeviceSharding(devices[0])
    x = jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32, sharding=one)
    variables = jax.eval_shape(
        lambda: fused.init(jax.random.key(0), jnp.zeros((1, 224, 224, 3)),
                           train=False))
    variables = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one),
        variables)
    _report("resnet50 b8 eval forward, fused conv+BN+ReLU",
            jax.jit(lambda v, im: fused.apply(v, im, train=False)).lower(
                variables, x))


if __name__ == "__main__":
    main()
