"""Device-mesh construction and sharding helpers.

The reference's topology model is one process per GPU with a fully replicated
model (DDP, train.py:128). The TPU-native model is a named
``jax.sharding.Mesh`` over the pod slice with a ``data`` axis (batch sharding —
the DDP equivalent) and a ``model`` axis (tensor sharding — reserved so TP is a
config change, SURVEY.md §2c). ``jax.make_mesh`` lays the axes onto the
physical ICI torus so the heavy ``data``-axis collectives ride neighbor links.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuic.config import MeshConfig


def make_mesh(cfg: Optional[MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a (data, model) mesh over all devices.

    cfg.data == 0 infers the data-axis size as n_devices / model. jax.make_mesh
    picks an ICI-friendly device order on real TPU slices; on CPU test meshes
    the order is row-major over jax.devices().
    """
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    seq, model = max(1, cfg.seq), max(1, cfg.model)
    if n % (seq * model):
        raise ValueError(f"seq*model axes {seq}x{model} do not divide "
                         f"device count {n}")
    data = cfg.data or n // (seq * model)
    shape = (data, seq, model)
    if data * seq * model != n:
        raise ValueError(f"mesh {shape} != device count {n}")
    # Auto axis types: shardings constrain data layout and GSPMD propagates /
    # inserts collectives (make_mesh defaults to Explicit sharding-in-types,
    # which instead demands out_sharding annotations on every contraction
    # touching a sharded dim — not the model we want).
    return jax.make_mesh(shape, tuple(cfg.axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices)


def replica_mesh(replicas: int, cfg: Optional[MeshConfig] = None,
                 devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """An R-replica data-parallel mesh over the FIRST ``replicas`` replica
    slots — the elastic re-form constructor (docs/parallelism.md,
    "Elastic data parallelism").

    ``make_mesh`` demands that the axes cover every device; a fleet that
    just lost a replica needs the opposite: the same (seq, model) inner
    shape laid over ``replicas`` of the surviving replica slots, with the
    rest of the host's devices idle. Each replica slot is ``seq*model``
    consecutive devices, so shrinking R keeps every surviving replica's
    inner axes on the same devices (no param migration inside a
    replica — only the data axis narrows, which is exactly what the
    ZeRO-sharded optimizer state reshards over on the capped restore)."""
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    replicas = int(replicas)
    if replicas < 1:
        raise ValueError(f"replica_mesh needs >= 1 replica (got {replicas})")
    per_replica = max(1, cfg.seq) * max(1, cfg.model)
    need = replicas * per_replica
    if need > len(devices):
        raise ValueError(
            f"replica_mesh: {replicas} replicas x {per_replica} devices "
            f"each = {need} devices, but only {len(devices)} available")
    return make_mesh(dataclasses.replace(cfg, data=replicas),
                     devices=devices[:need])


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding over the data axis — the DDP-equivalent layout."""
    return NamedSharding(mesh, P("data"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated layout (params/opt state under pure DP)."""
    return NamedSharding(mesh, P())


def local_batch_slice(global_batch: int, mesh: Mesh) -> int:
    """Per-process share of a global batch under data sharding."""
    procs = jax.process_count()
    if global_batch % procs:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"{procs} processes")
    return global_batch // procs
