"""Multi-host runtime initialization.

TPU-native replacement for the reference's NCCL process-group setup
(train.py:99-106):

- ``dist.init_process_group('nccl', rank=local_rank)`` + env-var rendezvous
  becomes ``jax.distributed.initialize()`` — the TPU runtime discovers the pod
  slice topology itself; no MASTER_ADDR/PORT plumbing.
- ``torch.cuda.set_device(local_rank)`` has no equivalent: one JAX process per
  host addresses all of its local chips; device binding is the mesh's job.
- ``args.distributed = world_size >= 1`` (reference train.py:104 — always True,
  a latent bug) becomes an honest ``is_distributed`` = process_count > 1 or
  device_count > 1.

Collectives are never issued eagerly from Python the way torch.distributed
does; they are traced into the jitted step and lowered by XLA onto ICI
(intra-slice torus) / DCN (across slices).
"""

from __future__ import annotations

import dataclasses
import os

import jax


@dataclasses.dataclass(frozen=True)
class RuntimeInfo:
    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int
    platform: str
    device_kind: str
    # Why jax.distributed.initialize() was called in this process; '' when
    # it was not (a single host). Entry points print it.
    distributed: str = ""

    @property
    def is_distributed(self) -> bool:
        return self.process_count > 1 or self.global_device_count > 1


_initialized = False

# Env markers whose presence means a cluster launcher started this process and
# jax.distributed can auto-discover the topology (TPU pod runtime, GKE
# JobSet, or an explicit coordinator address).
_CLUSTER_ENV_MARKERS = ("TPU_WORKER_HOSTNAMES", "JAX_COORDINATOR_ADDRESS",
                        "COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS")


def _cluster_marker() -> str:
    """The launcher marker that says this is one process of several, or
    '' on a single host (one worker hostname, no coordinator address)."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if hosts and len(hosts.split(",")) > 1:
        return f"TPU_WORKER_HOSTNAMES lists {len(hosts.split(','))} hosts"
    for m in _CLUSTER_ENV_MARKERS[1:]:
        if os.environ.get(m):
            return f"{m} is set"
    return ""


_init_reason = ""


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> RuntimeInfo:
    """Initialize the multi-host runtime (idempotent).

    jax.distributed.initialize() is called when (a) explicit coordinator
    arguments are given, (b) TPUIC_NUM_PROCESSES > 1, or (c) a cluster
    launcher's environment markers are present (multi-worker TPU pod /
    explicit coordinator address) — in case (c) with no arguments, letting
    JAX auto-discover the topology. Plain single-process runs skip it.

    Launchers without a cluster runtime (CPU fleets, the CI fleet smoke
    — scripts/fleet_smoke.py; the gang supervisor's ``--coordinator``
    path, runtime/gang.py) pass the rendezvous through the environment
    instead of code: TPUIC_COORDINATOR_ADDRESS + TPUIC_NUM_PROCESSES +
    TPUIC_PROCESS_ID fill any argument the caller left None, so
    ``python train.py`` joins a fleet without new flags. Explicit
    arguments always win over the env. A HALF-set env rendezvous
    (coordinator or process id without the full trio resolvable) raises
    instead of silently falling back to auto-detection — the same loud
    failure as telemetry/fleet.py's ``tag_bus_with_rank``: half a fleet
    identity is not an identity, and k workers silently collapsing to
    auto-discovered rank 0/1 would wedge the rendezvous (or worse,
    train as the wrong fleet) with nothing in the logs.
    """
    global _initialized, _init_reason
    env_addr = os.environ.get("TPUIC_COORDINATOR_ADDRESS") or None
    env_num = os.environ.get("TPUIC_NUM_PROCESSES") or None
    env_pid = os.environ.get("TPUIC_PROCESS_ID") or None
    if coordinator_address is None:
        coordinator_address = env_addr
    if num_processes is None and env_num:
        num_processes = int(env_num)
    if process_id is None and env_pid is not None:
        process_id = int(env_pid)
    if (env_addr is not None or env_pid is not None) and (
            coordinator_address is None or num_processes is None
            or process_id is None):
        # TPUIC_NUM_PROCESSES alone stays valid (the documented
        # auto-discovery trigger); naming a coordinator or a process id
        # commits the launcher to the full trio.
        raise ValueError(
            f"TPUIC env rendezvous is half-set: TPUIC_COORDINATOR_ADDRESS="
            f"{env_addr!r}, TPUIC_NUM_PROCESSES={env_num!r}, "
            f"TPUIC_PROCESS_ID={env_pid!r} — a launcher must set all "
            "three (or none; TPUIC_NUM_PROCESSES alone keeps the "
            "auto-discovery path)")
    reason = ("coordinator address given" if coordinator_address is not None
              else f"num_processes={num_processes}"
              if num_processes not in (None, 1) else _cluster_marker())
    if reason and not _initialized:
        _init_reason = reason
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
    return runtime_info()


def runtime_info() -> RuntimeInfo:
    return RuntimeInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
        platform=jax.devices()[0].platform,
        device_kind=jax.devices()[0].device_kind,
        distributed=_init_reason,
    )


def data_parallel_replicas() -> int:
    """The CURRENT data-parallel extent of this process's fleet.

    Elastic runs (runtime/gang.py elastic mode) publish the live
    membership via ``TPUIC_MEMBERSHIP_FILE`` — its ``active`` count is
    the R the fleet is actually running at, which may be below the
    configured world mid-degrade. Without a membership file, the
    launcher's ``TPUIC_FLEET_RANKS`` override wins (independent-rank CPU
    fleets), then the live ``jax.process_count()``. Poll-cheap (one
    stat + read only when the file moved is the watcher's job; this is
    the one-shot read for wiring/telemetry, not the hot loop)."""
    from tpuic.runtime.membership import ENV_MEMBERSHIP_FILE, read_membership
    path = os.environ.get(ENV_MEMBERSHIP_FILE, "")
    if path:
        m = read_membership(path)
        if m is not None:
            return max(1, m.replicas)
    ranks = os.environ.get("TPUIC_FLEET_RANKS")
    if ranks:
        return max(1, int(ranks))
    return jax.process_count()
