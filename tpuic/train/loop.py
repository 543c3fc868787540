"""Epoch driver — the re-design of reference train.py:99-188.

Maps 1:1 onto the reference's flow with the TPU-shaped replacements:

| reference                                  | here                              |
|--------------------------------------------|-----------------------------------|
| init_process_group('nccl') (train.py:102)  | runtime.initialize() + make_mesh  |
| DataLoader + DistributedSampler (112-118)  | tpuic.data.Loader (sharded)       |
| Classifier + SyncBN + DDP (122-128)        | create_model + sharded jit step   |
| checkpoint probe/partial load (131-153)    | CheckpointManager.restore_into    |
| MultiStepLR + weighted CE (156-158)        | optax schedule + loss config      |
| for epoch in range(100) (161)              | fit() — resumes at saved epoch    |
| train_epoch / val_epoch (36-97)            | train_epoch / val_epoch           |
| best/latest saves (173-188)                | save_best / maybe_save_latest     |

Progress UX matches the reference: host-0 tqdm bar with description
``Epoch: {e}; Loss {val:.4f}|({avg:.4f})`` (train.py:67-68) and val print
(train.py:94-95). The displayed loss is already the global mean — the step
computes it over the global batch, so no extra logging collective exists.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import time
from typing import Optional

_T_IMPORT = time.perf_counter()     # the 'import' span of this module

import jax
import numpy as np
from tqdm import tqdm

from tpuic.runtime import faults as _faults
from tpuic.telemetry.events import publish as _tm_publish
from tpuic.telemetry.spans import (annotation as _annotation,
                                   record as _record_span, span as _span)

from tpuic.checkpoint.manager import CheckpointManager
from tpuic.config import Config
from tpuic.data.folder import ImageFolderDataset
from tpuic.data.pipeline import Loader
from tpuic.metrics.logging import MetricLogger, host0_print, is_host0
from tpuic.metrics.meters import AverageMeter
from tpuic.models import create_model_from_config
from tpuic.runtime.mesh import make_mesh, replicated_sharding
from tpuic.telemetry.profile import note as _note_program
from tpuic.train.optimizer import make_optimizer, make_schedule
from tpuic.train.state import create_train_state
from tpuic.train.step import STEP_METRICS, make_eval_step, make_train_step

# What this module's import graph cost beyond what the caller had already
# imported (docs/observability.md, "Spans").
_record_span("import", _T_IMPORT, time.perf_counter(), module=__name__)


def _async_copy(tree) -> None:
    """Start device->host transfers for every array in a metrics dict so the
    later (deferred) device_get returns from the transfer cache instead of
    blocking on the device. Tolerates plain floats (tests with stub steps)."""
    for h in jax.tree_util.tree_leaves(tree):
        if hasattr(h, "copy_to_host_async"):
            h.copy_to_host_async()


class Trainer:
    def __init__(self, cfg: Config, mesh=None, log_dir: Optional[str] = None):
        with _span("trainer.init", model=cfg.model.name) as sp:
            self._init(cfg, mesh, log_dir)
            sp.attrs["chips"] = self.mesh.size

    def _init(self, cfg: Config, mesh, log_dir: Optional[str]) -> None:
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh)
        # On a single device the step is jitted without the mesh and its
        # sharding annotations; they engage only when there is something
        # to shard over.
        step_mesh = self.mesh if self.mesh.size > 1 else None
        d = cfg.data
        with _span("trainer.data") as sp:
            self.train_ds = ImageFolderDataset(d.data_dir, "train", d.resize_size, d)
            self.val_ds = ImageFolderDataset(d.data_dir, "val", d.resize_size, d,
                                             class_to_idx=self.train_ds.class_to_idx)
            if d.pack:
                # Decode-once packed cache + device-side augmentation: the only
                # way a 1-core host feeds the chip (tpuic/data/pack.py docstring).
                from tpuic.data.pack import pack_dataset
                cache = d.cache_dir or os.path.join(d.data_dir, ".tpuic_pack")
                self.train_ds = pack_dataset(self.train_ds, cache,
                                             verbose=is_host0())
                self.val_ds = pack_dataset(self.val_ds, cache, verbose=is_host0())
            global_batch = self._build_loaders()
            sp.attrs["images"] = len(self.train_ds)
        with _span("trainer.state_init") as sp:
            num_classes = cfg.model.num_classes or self.train_ds.num_classes
            mcfg = cfg.model
            if num_classes != mcfg.num_classes:
                mcfg = dataclasses.replace(mcfg, num_classes=num_classes)
            # Mixed-precision policy (docs/performance.md "Mixed-precision
            # training"): compute_dtype is the one knob — it forces the flax
            # forward dtype, the train step's batch cast and f32-loss guard
            # (train/step.py), and the dtype-aware MFU roofline below. Master
            # weights, optimizer moments, and checkpoints stay f32 regardless
            # (param_dtype is untouched), so lifecycle/swap/elastic machinery
            # never sees a bf16 artifact.
            from tpuic.config import resolve_compute_dtype
            compute_dtype = resolve_compute_dtype(mcfg)
            if compute_dtype:
                mcfg = dataclasses.replace(
                    mcfg, dtype=("bfloat16" if compute_dtype == "bf16"
                                 else "float32"))
            if cfg.optim.auto_class_weights:
                # Inverse-frequency CE weights from the train fold (what the
                # reference's hand-tuned [3,3,10,1,4,4,5] approximated for its
                # own dataset): w_c = N / (K_present * n_c), mean ~1 over the
                # classes that actually occur. Sized by the RESOLVED head width
                # so an explicit --num-classes larger than the fold's class
                # count pads with weight 1.0 instead of tracing a shape error.
                counts = self.train_ds.class_counts()
                if len(counts) > num_classes:
                    raise ValueError(
                        f"auto class weights: train fold has {len(counts)} "
                        f"classes but the model head is {num_classes} wide")
                counts = np.concatenate(
                    [counts, np.zeros(num_classes - len(counts), np.int64)])
                w = np.ones(num_classes, np.float64)
                present = counts > 0
                w[present] = counts.sum() / (present.sum() * counts[present])
                cfg = dataclasses.replace(cfg, optim=dataclasses.replace(
                    cfg.optim,
                    class_weights=tuple(round(float(x), 6) for x in w)))
                self.cfg = cfg
                host0_print("[weights] auto class weights: "
                            + ", ".join(f"{c}={x:.3f}" for c, x in
                                        zip(self.train_ds.classes,
                                            cfg.optim.class_weights)))
            self.mcfg = mcfg  # resolved model config (inferred num_classes)
            self.model = create_model_from_config(mcfg, mesh=self.mesh)
            steps = max(1, self.train_loader.steps_per_epoch())
            self.schedule = make_schedule(cfg.optim, steps, cfg.run.epochs,
                                          global_batch=global_batch)
            tx = make_optimizer(cfg.optim, steps, cfg.run.epochs,
                                global_batch=global_batch)
            shape = (global_batch, d.resize_size, d.resize_size, 3)
            with self.mesh:
                self.state = create_train_state(
                    self.model, tx, jax.random.key(cfg.run.seed), shape,
                    ema=cfg.optim.ema_decay > 0)
            from tpuic.utils import tree_bytes, tree_size
            # What persists between steps, from shapes alone (no device
            # work): the benchmark's step_transient_gib subtracts them
            # from the peak.
            sp.attrs["param_bytes"] = tree_bytes(self.state.params)
            sp.attrs["opt_state_bytes"] = tree_bytes(self.state.opt_state)
            host0_print(f"[model] {mcfg.name}: "
                        f"{tree_size(self.state.params) / 1e6:.1f}M params "
                        f"({tree_bytes(self.state.params) / (1 << 20):.1f} MB), "
                        f"{num_classes} classes, global batch {global_batch}")
            # TP/FSDP state sharding (replicated when neither is requested —
            # reference DDP semantics).
            self.state_sharding = None
            if step_mesh is not None and (cfg.mesh.fsdp or cfg.mesh.zero1 or (
                    cfg.mesh.tensor_parallel and self.mesh.shape["model"] > 1)):
                from tpuic.parallel.sharding import shard_state, state_shardings
                self.state_sharding = state_shardings(
                    self.state, self.mesh, tp=cfg.mesh.tensor_parallel,
                    fsdp=cfg.mesh.fsdp, zero1=cfg.mesh.zero1)
                self.state = shard_state(self.state, self.state_sharding)
            elif step_mesh is not None:
                # Pure data parallelism: the state starts where every step
                # leaves it, committed and replicated over the mesh. Left
                # uncommitted on the default device, step 1 and step 2 would
                # carry different input shardings and compile the step twice.
                self.state = jax.device_put(self.state,
                                            replicated_sharding(self.mesh))
        with _span("trainer.build_steps"):
            self._build_steps()
        self.last_misclassified: list = []
        self.last_counters: dict = {}   # the family's own; see the drain
        with _span("trainer.checkpoint"):
            self.ckpt = CheckpointManager(cfg.run.ckpt_dir, mcfg.name,
                                          cfg.run.save_period,
                                          async_commit=cfg.run.async_checkpoint)
            if is_host0():
                # Reproducibility sidecar: the resolved config (incl. inferred
                # num_classes / derived class weights) next to the checkpoint
                # tracks. tpuic.predict reads it to auto-resolve the model.
                resolved = dataclasses.replace(cfg, model=mcfg)
                with open(os.path.join(self.ckpt.root, "config.json"), "w") as f:
                    json.dump(dataclasses.asdict(resolved), f, indent=2,
                              default=str)
                # Class-name sidecar: online serving (tpuic.serve) has no fold
                # tree to derive display names from at request time.
                with open(os.path.join(self.ckpt.root,
                                       "class_to_idx.json"), "w") as f:
                    json.dump(self.train_ds.class_to_idx, f, indent=2)
            # SIGTERM (pod preemption / scheduler eviction) -> finish the
            # current step, flush a 'latest' checkpoint, return cleanly
            # (runtime/preemption.py). The handler is installed for the span of
            # fit() only; polling is a flag read per step, with a cross-host
            # agreement at fixed boundaries on multi-host pods.
            from tpuic.runtime.preemption import PreemptionGuard
            self.preemption = PreemptionGuard()
            # Elastic fleet membership (runtime/membership.py, docs/
            # parallelism.md "Elastic data parallelism"): when the elastic
            # gang supervisor injected TPUIC_MEMBERSHIP_FILE, the loop polls
            # it at step boundaries (one os.stat when unchanged) and a
            # 'degrade' transition re-forms THIS process in place — restore
            # from the fleet-agreed step through the capped integrity
            # ladder, recompile if the local mesh shrank — with no process
            # restart. None (the common case) costs nothing.
            from tpuic.runtime.membership import MembershipWatcher
            self.membership = MembershipWatcher.from_env()
            self._reform_pending = None
            self.reforms = 0
            self.logger = MetricLogger(log_dir)
            self.start_epoch = 0
            # Step offset into start_epoch (step-exact resume from a mid-epoch
            # preemption flush); 0 for normal end-of-epoch checkpoints.
            self.start_step = 0
            self.best_score = 0.0
            if cfg.run.init_from:
                self._init_from_torch(cfg.run.init_from)
            if cfg.run.resume:
                # Newest of latest/best — a crash after the last val improvement
                # resumes at the last periodic save instead of replaying epochs.
                self.state, self.start_epoch, self.best_score = \
                    self.ckpt.restore_into(self.state)
                self.start_step = self._validated_start_step()
                if self.state_sharding is not None:
                    from tpuic.parallel.sharding import shard_state
                    self.state = shard_state(self.state, self.state_sharding)
        # Telemetry (docs/observability.md): step-time breakdown, goodput
        # accounting, optional JSONL event sink / trace trigger /
        # TensorBoard bridge — all host-side subscribers on the global
        # event bus (zero device syncs, zero compiles; test-asserted).
        from tpuic import telemetry as _telemetry
        self.telemetry = _telemetry.TrainTelemetry(
            cfg.run, model_name=mcfg.name, image_size=d.resize_size,
            global_batch=global_batch, n_devices=self.mesh.size,
            device=jax.devices()[0], tb=self.logger.tb,
            compute_dtype=(compute_dtype or (
                "bf16" if mcfg.dtype == "bfloat16" else "f32")))
        # Non-finite rollback bookkeeping (docs/robustness.md): the jitted
        # step skips poisoned updates in-graph (train/step.py guard) and
        # counts the consecutive-skip streak in state.skip_count; the
        # deferred log drain watches the streak and, past
        # run.skip_threshold, flags a rollback — fit() then restores the
        # last good checkpoint through the integrity ladder and continues.
        self._rollback_pending = False
        self.rollbacks = 0
        self._quarantine_seen = 0
        self._last_skip_streak = 0
        self._steps_exhausted = False

    def _init_from_torch(self, path: str) -> None:
        """Pretrained-weight initialization from a torch checkpoint.

        The reference starts every backbone pretrained (nn/classifier.py:9-21);
        the conversion + lenient merge (and the *-s2d stem re-indexing) is
        shared with tpuic.predict in checkpoint.torch_convert."""
        from tpuic.checkpoint.torch_convert import init_state_from_torch

        self.state = init_state_from_torch(self.state, path,
                                           self.cfg.model.name,
                                           log=host0_print)
        if self.state_sharding is not None:
            from tpuic.parallel.sharding import shard_state
            self.state = shard_state(self.state, self.state_sharding)

    def _build_loaders(self) -> int:
        """Train/val Loaders for the CURRENT ``self.mesh`` — ONE
        construction site shared by ``__init__`` and the elastic re-form
        (``_rebuild_for_replicas``), so the two paths cannot drift: the
        global batch is per-device batch x data extent, the device-cache
        HBM budget is a per-process TOTAL (train claims first, val gets
        the remainder — never 2x the configured budget), and a fold
        smaller than one global batch fails loudly. Returns the global
        batch."""
        d = self.cfg.data
        step_mesh = self.mesh if self.mesh.size > 1 else None
        n_data = self.mesh.shape["data"]
        global_batch = d.batch_size * n_data
        cache_total = int(d.device_cache_mb) << 20
        self.train_loader = Loader(self.train_ds, global_batch, step_mesh,
                                   seed=d.shuffle_seed,
                                   num_workers=d.num_workers,
                                   prefetch=d.prefetch, drop_last=True,
                                   device_cache_bytes=cache_total,
                                   augment=None if d.augment else False)
        if self.train_loader.steps_per_epoch() == 0:
            # drop_last with a fold smaller than ONE global batch would
            # otherwise train zero steps per epoch while still writing
            # checkpoints and reporting val numbers — a silent no-op run.
            raise ValueError(
                f"train fold has {len(self.train_ds)} images but the "
                f"global batch is {global_batch} "
                f"({d.batch_size}/chip x {n_data} data-parallel devices): "
                "every epoch would train ZERO steps (the trailing partial "
                "batch is dropped). Reduce --batchsize or the device "
                "count, or add data.")
        self.val_loader = Loader(self.val_ds,
                                 d.resolved_val_batch_size() * n_data,
                                 step_mesh, shuffle=False,
                                 num_workers=d.num_workers,
                                 prefetch=d.prefetch,
                                 device_cache_bytes=max(
                                     0, cache_total
                                     - self.train_loader.resident_bytes))
        return global_batch

    def _close_loaders(self) -> None:
        """End the loaders' parked producers (Loader.close): called where
        the Trainer drops its loaders or is done with them."""
        self.train_loader.close()
        self.val_loader.close()

    def _step_program_keys(self):
        """Registry keys of THE train/eval step programs for the current
        geometry (tpuic.compiled, docs/performance.md "Compiled-program
        registry").  The key pins everything the built step closes over
        — optimizer config (schedule params, guard, loss scale, class
        weights, ema), seed, eval flags, sharding flags, the donation
        policy verdict — plus the loader geometry the schedule was
        derived from, the mesh signature, and the batch avals.  An
        elastic reform back to a previously-seen extent therefore HITS
        (aval-identical executables reused instead of re-jitting); any
        geometry/config change misses and the superseded key is evicted."""
        import dataclasses as _dc

        from tpuic.compiled import ProgramKey, donation_allowed, stable_crc
        cfg = self.cfg
        d = cfg.data
        steps = max(1, self.train_loader.steps_per_epoch())
        global_batch = self.train_loader.global_batch
        mesh_sig = (tuple((str(a), int(n)) for a, n in
                          self.mesh.shape.items())
                    if self.mesh.size > 1 else ())
        cfg_crc = stable_crc({
            "optim": _dc.asdict(cfg.optim), "model": _dc.asdict(self.mcfg),
            "mesh_cfg": _dc.asdict(cfg.mesh), "seed": cfg.run.seed,
            "epochs": cfg.run.epochs, "steps_per_epoch": steps,
            "collect": cfg.run.collect_misclassified,
            "per_class": cfg.run.per_class_metrics,
            "donate": donation_allowed(
                guard_active=bool(cfg.optim.skip_nonfinite)),
        })
        shapes = ((global_batch, d.resize_size, d.resize_size, 3), cfg_crc)
        return tuple(
            ProgramKey(model=f"train:{self.mcfg.name}:{kind}",
                       shapes=shapes, mesh=mesh_sig, dtype=self.mcfg.dtype)
            for kind in ("step", "eval"))

    def _build_steps(self) -> None:
        """(Re-)build the train/eval steps for the CURRENT mesh,
        schedule, and state sharding — shared by ``__init__`` and the
        elastic re-form — through the compiled-program registry
        (tpuic/compiled): a reform whose geometry matches an existing
        key reuses the aval-identical jitted step (and its warm XLA
        cache) instead of re-jitting, a changed geometry builds fresh
        and evicts the pre-reform entries."""
        from tpuic.compiled import registry as _registry
        cfg = self.cfg
        step_mesh = self.mesh if self.mesh.size > 1 else None
        train_key, eval_key = self._step_program_keys()
        self.train_step = _registry.get_or_compile(
            train_key,
            lambda: make_train_step(cfg.optim, self.mcfg, step_mesh,
                                    lr_schedule=self.schedule,
                                    seed=cfg.run.seed,
                                    state_sharding=self.state_sharding),
        ).executable
        self.eval_step = _registry.get_or_compile(
            eval_key,
            lambda: make_eval_step(
                cfg.optim, self.mcfg, step_mesh,
                state_sharding=self.state_sharding,
                per_sample=cfg.run.collect_misclassified,
                per_class=cfg.run.per_class_metrics),
        ).executable
        # Pre-reform GC: a superseded geometry's step entries can never
        # run again in this process.
        for old in getattr(self, "_step_keys", ()):
            if old not in (train_key, eval_key):
                _registry.evict(old)
        self._step_keys = (train_key, eval_key)
        # Prewarm manifest (docs/performance.md): when the supervisor —
        # or any caller — exported TPUIC_COMPILE_MANIFEST, persist the
        # keys this process compiled so the NEXT process (a restarted
        # gang member) prewarms them up front.  ``_manifest_preexisting``
        # (first call only) records whether a previous life already left
        # a manifest behind — that is what gates the restart-side
        # prewarm in fit().
        mpath = os.environ.get("TPUIC_COMPILE_MANIFEST", "")
        if mpath:
            if not hasattr(self, "_manifest_preexisting"):
                self._manifest_preexisting = os.path.exists(mpath)
            try:
                _registry.write_manifest(mpath)
            except OSError as e:
                host0_print(f"[compiled] could not write prewarm "
                            f"manifest {mpath}: {e}")

    def prewarm(self, manifest_path: Optional[str] = None) -> dict:
        """Compile-and-execute every program this run's steady state
        needs BEFORE the first training step (docs/performance.md,
        "Compiled-program registry") — the restart path that turns
        first-step compile stalls into up-front prewarm time, measured
        in perf/resume_cache_proof.json and checker-asserted (zero
        steady-state compiles after prewarm) in the CI prewarm smoke.

        One real batch is pulled from each loader (the same batch fit()
        will see first — the epoch permutation and augment streams are
        position-keyed and stateless, so nothing is consumed or
        perturbed) and run through the train step against a THROWAWAY
        copy of the state (the step is functional and the copy absorbs
        donation) and through the eval step directly.  Executing — not
        just lowering — is what populates the jit caches and forces the
        backend compiles (disk reads when the persistent XLA cache is
        warm), so the subsequent fit dispatches with zero compiles.

        ``manifest_path`` names a prewarm manifest to cross-check: a
        corrupt manifest raises :class:`tpuic.compiled.ManifestError`
        (refusal — never prewarm from a torn file); a manifest that
        does not list this run's keys is reported but does not block
        (the geometry is local knowledge; the manifest is the fleet's
        memory of it)."""
        with _span("prewarm"):
            return self._prewarm(manifest_path)

    def _prewarm(self, manifest_path: Optional[str]) -> dict:
        from tpuic.compiled import ProgramKey, load_manifest
        from tpuic.compiled import registry as _registry
        t0 = time.perf_counter()
        listed = None
        if manifest_path:
            listed = {ProgramKey.from_dict(e["key"])
                      for e in load_manifest(manifest_path)}
        keys = getattr(self, "_step_keys", ())
        covered = (None if listed is None
                   else sum(1 for k in keys if k in listed))
        it = self.train_loader.epoch(self.start_epoch,
                                     start_step=self.start_step)
        try:
            batch = next(it)
        finally:
            it.close()
        fbatch = {k: batch[k] for k in ("image", "label", "mask")}
        # Donation-safe copy: the guard-off path donates the state
        # argument, so the real self.state must never be passed here.
        # The copy must be SIGNATURE-FAITHFUL leaf by leaf — a restored
        # state mixes numpy leaves with committed/uncommitted jax
        # Arrays, and coercing a numpy leaf to a jax Array changes the
        # pjit call signature: fit's first step would then backend-
        # compile a second executable (no retrace, so invisible to
        # trace counters) and the prewarm would not be compile-flat.
        # jnp.copy preserves sharding and committed-ness for jax
        # Arrays; numpy stays numpy; host scalars are immutable.
        import jax.numpy as jnp

        def _leaf_copy(x):
            if isinstance(x, jax.Array):
                return jnp.copy(x)
            if isinstance(x, np.ndarray):
                return np.copy(x)
            return x

        state_copy = jax.tree_util.tree_map(_leaf_copy, self.state)
        # TWO train-step executions, because a resumed run dispatches
        # under TWO distinct program signatures and both must be warm:
        #  1. the RESTORED signature — a checkpoint-restored state mixes
        #     numpy and uncommitted-jax scalar leaves (step, skip_count),
        #     which pjit resolves to unspecified input shardings; fit's
        #     first step runs under this signature, and
        #  2. the STEADY-STATE signature — every later step passes the
        #     previous step's output, whose leaves are all committed jax
        #     Arrays, so the same avals resolve to concrete shardings: a
        #     different lowering key and a different executable.
        # Warming only (1) leaves fit's SECOND step to backend-compile
        # (the stall moves one step later instead of disappearing).
        # Feeding call 1's output state into call 2 reproduces (2)
        # exactly; both calls run against throwaway state (donation-safe).
        out_state, m = self.train_step(state_copy, fbatch)
        jax.block_until_ready(m["loss"])
        out2_state, m2 = self.train_step(out_state, fbatch)
        jax.block_until_ready(m2["loss"])
        del out_state, state_copy
        vit = self.val_loader.epoch(0)
        try:
            vbatch = next(vit)
        finally:
            vit.close()
        vfbatch = {k: vbatch[k] for k in ("image", "label", "mask")}
        # Same two-signature rule for eval: fit's epoch-end eval sees the
        # post-step (all-committed) state; an eval before any step (a
        # resume landing exactly on an epoch boundary) sees the restored
        # one. keep_unused DCE usually collapses the two eval signatures
        # into one, but that is a jaxpr property, not a contract.
        em = self.eval_step(out2_state, vfbatch)
        jax.block_until_ready(em["count"])
        em2 = self.eval_step(self.state, vfbatch)
        jax.block_until_ready(em2["count"])
        del out2_state
        for k in keys:
            _registry.mark_prewarmed(k)
        prewarm_s = time.perf_counter() - t0
        out = {"prewarm_s": round(prewarm_s, 3), "programs": len(keys),
               "manifest_listed": covered}
        if covered is not None and covered < len(keys):
            host0_print(f"[compiled] prewarm manifest lists {covered}/"
                        f"{len(keys)} of this run's step programs "
                        f"(geometry changed since it was written)")
        host0_print(f"[compiled] prewarmed {len(keys)} step programs in "
                    f"{prewarm_s:.1f}s")
        _tm_publish("compile_cache", action="prewarm_done",
                    programs=len(keys), manifest_listed=covered,
                    duration_s=round(prewarm_s, 3))
        return out

    def _loader_geometry(self):
        """(global_batch, seed, n_samples) — everything the epoch
        permutation and its step slicing depend on; recorded at a
        mid-epoch flush and required to match before a resume reuses the
        step offset."""
        ld = self.train_loader
        return (ld.global_batch, ld.seed, len(ld.dataset))

    def _validated_start_step(self) -> int:
        """Step offset of the checkpoint the manager just restored, IF its
        recorded loader geometry matches this run (shared by __init__
        resume and the non-finite rollback — both must refuse an offset
        that would skip the wrong samples)."""
        start_step = self.ckpt.last_restore_step_in_epoch or 0
        if not start_step:
            return 0
        saved = self.ckpt.last_restore_geometry
        live = self._loader_geometry()
        epoch = (self.ckpt.last_restore_meta or (0, 0))[0]
        if saved is not None and any(
                a not in (-1, b) for a, b in zip(saved, live)):
            # The epoch permutation is keyed by (seed, n_samples)
            # and sliced by global_batch — a mismatch in any means
            # the offset points at different samples.
            host0_print(
                f"[ckpt] mid-epoch checkpoint was flushed under "
                f"loader geometry (global_batch, seed, n_samples)="
                f"{saved} but this run has {live} — the step "
                f"offset would skip the wrong samples; replaying "
                f"epoch {epoch} from its start instead")
            return 0
        if start_step > len(self.train_loader):
            host0_print(
                f"[ckpt] mid-epoch step {start_step} exceeds "
                f"this run's {len(self.train_loader)} steps/epoch "
                f"(dataset changed?) — replaying epoch "
                f"{epoch} from its start instead")
            return 0
        return start_step

    # -- epochs -------------------------------------------------------------
    def train_epoch(self, epoch: int, start_step: int = 0) -> float:
        """Reference train_epoch (train.py:36-73).

        ``start_step`` continues a partially-trained epoch at that step
        (step-exact resume; the loader serves the identical remainder).
        ``self.last_epoch_steps`` records how many steps of this epoch are
        complete when the method returns — = steps_per_epoch normally,
        less if preemption broke the loop — for the mid-epoch flush."""
        with _span("train_epoch", epoch=epoch) as sp:
            loss = self._train_epoch(epoch, start_step)
            sp.attrs["steps"] = self.last_epoch_steps - start_step
            # the family's counters (a looped model's exits, a routed
            # layer's pairs and load): the epoch's last drained values
            sp.attrs.update(getattr(self, "last_counters", {}))
        return loss

    def _train_epoch(self, epoch: int, start_step: int) -> float:
        with _span("epoch.head", epoch=epoch):
            losses = AverageMeter()
            remaining = len(self.train_loader) - start_step
            self.last_epoch_steps = start_step
            # Step-time breakdown (telemetry/steptime.py): the wrapped
            # iterator times loader waits (data-wait), dispatch is timed
            # around the step call below, and the residual is device time —
            # pure perf_counter arithmetic, no host syncs added.
            steptime = self.telemetry.steptime
            steptime.epoch_start()
            it = steptime.wrap_epoch(
                self.train_loader.epoch(epoch, start_step=start_step))
            bar = tqdm(it, total=remaining, disable=not is_host0())
            metrics = None
            log_every = max(1, self.cfg.run.log_every_steps)
            global_batch = self.train_loader.global_batch
            # One readback per EPOCH for the optimizer step counter: the in-loop
            # step number is step0 + host steps, so logging never touches
            # state.step on the hot path (each device_get is a blocking sync).
            step0 = int(jax.device_get(self.state.step))  # tpuic-ok: TPU101 one read per EPOCH, off the steady-state path
            # Deferred logging: at log point N we SCHEDULE an async device->host
            # copy of the interval's metrics and DRAIN log point N-1, whose
            # values the device finished an interval ago — so the drain returns
            # from the transfer cache instead of stalling dispatch. The loop
            # still cannot run away from the device: draining point N-1 throttles
            # the host to at most one interval of run-ahead, which keeps the
            # measured images/sec honest.
            pending = None  # (host step number, images/sec, metric handles)
            t_log = time.perf_counter()
            from tpuic.runtime.preemption import agree
            preempt_on = self.cfg.run.handle_preemption
            multi = jax.process_count() > 1
            # Multi-host: a locally-latched SIGTERM may only be acted on at a
            # boundary every host reaches together (agree() is a collective);
            # 16 steps of latency is well inside any grace window. With
            # handle_preemption off, no polling (and no allgather) happens.
            preempt_sync = 16
        for step, batch in enumerate(bar):
            # Fault-injection sites (runtime/faults.py; inert when unarmed):
            # 'sigterm' drives the REAL preemption path — the latch, the
            # boundary agreement, the mid-epoch flush — deterministically.
            if preempt_on and _faults.fire("sigterm", step=step0 + step):
                os.kill(os.getpid(), signal.SIGTERM)
            trig = preempt_on and self.preemption.triggered
            if preempt_on and multi:
                if step % preempt_sync == 0:
                    trig = agree(trig)
                    if trig:
                        self.preemption.trigger()  # latch the agreement
                else:
                    trig = False  # never act unilaterally between boundaries
            if trig:
                bar.close()
                break
            if self.membership is not None:
                m = self.membership.poll()
                if m is not None:
                    if m.reason == "degrade" or (
                            self.membership.skipped
                            and m.resume_step is not None):
                        # A peer died: re-form from the fleet-agreed
                        # step (fit() runs the restore) instead of
                        # training ahead of the membership the fleet
                        # just agreed on. The second arm is the
                        # coalesced case — the file holds only the
                        # latest view, so a degrade overwritten by its
                        # rejoin before this rank polled (a long val
                        # pass) surfaces as a rejoin with skipped
                        # versions and the cap aboard; restoring to the
                        # cap is a deterministic replay either way.
                        self._reform_pending = m
                        bar.close()
                        break
                    # rejoin/restart transitions need no restore here —
                    # note them so the stream shows the fleet view.
                    _tm_publish("reform", reason=m.reason,
                                version=m.version, active=list(m.active),
                                resume_step=m.resume_step, acted=False)
            fbatch = {k: batch[k] for k in ("image", "label", "mask")}
            if _faults.fire("nan_batch", step=step0 + step):
                # Poison this step's images host-side: same shapes/dtypes,
                # so the guard's zero-recompile contract is what's tested.
                fbatch["image"] = fbatch["image"] * np.float32("nan")
            if _faults.fire("slow_step", step=step0 + step):
                # Injected host-side stall (runtime/faults.py): a
                # deterministic step-time regression, for trace-trigger
                # tests — the step's work is untouched.
                slow_s = _faults.param("slow_step")
                # Explicit None check: '#0' must mean a 0 s stall (a
                # severity-sweep control run), not the default.
                time.sleep(
                    0.05 if slow_s is None else float(slow_s))  # tpuic-ok: TPU101 fault param is a host float
            if _faults.fire("hard_crash", step=step0 + step):
                # Abrupt process death: SIGKILL to self — no flush, no
                # atexit, no Python teardown. The supervisor
                # (runtime/supervisor.py) must classify this as a
                # retryable crash and restart with resume.
                os.kill(os.getpid(), signal.SIGKILL)
            if _faults.fire("hang_step", step=step0 + step):
                # Wedge: stop making progress while staying alive — the
                # shape of a stuck device call or a data-pipeline
                # deadlock. Only the supervisor's watchdog escalation
                # (SIGQUIT dump -> SIGTERM -> SIGKILL) ends it; the
                # cooperative SIGTERM latch is useless here by design
                # (the loop never reaches its next poll).
                hang_s = _faults.param("hang_step")
                deadline = (None if hang_s is None
                            else time.monotonic() + float(hang_s))  # tpuic-ok: TPU101 fault param is a host float
                while deadline is None or time.monotonic() < deadline:
                    time.sleep(0.5)
            if _faults.fire("rank_crash", step=step0 + step):
                # Rank-targeted SIGKILL (#PARAM names the victim rank,
                # default 0): one member of a gang dies abruptly while
                # its peers keep running — the partial failure the gang
                # supervisor (runtime/gang.py) must answer with a
                # coordinated teardown + restart. Every rank evaluates
                # the armed directive; only the named one dies.
                target = _faults.param("rank_crash")
                if int(self.telemetry.rank) == int(target or 0):
                    os.kill(os.getpid(), signal.SIGKILL)
            if _faults.fire("rank_hang", step=step0 + step):
                # Rank-targeted wedge (forever; #PARAM names the rank,
                # default 0): the partial-hang twin — only the gang's
                # per-rank watchdog escalation ends it.
                target = _faults.param("rank_hang")
                if int(self.telemetry.rank) == int(target or 0):
                    while True:
                        time.sleep(0.5)
            # The programs of a step, for a trace reader to map the ops
            # they ran to scopes after the fact (telemetry/profile.py):
            # once registered, a dict lookup each.
            _note_program("step", self.train_step, (self.state, fbatch))
            _note_program("input_prep",
                          getattr(self.train_loader, "prep_fn", None),
                          getattr(self.train_loader, "prep_specs", None))
            steptime.dispatch_start()
            self.state, metrics = self.train_step(self.state, fbatch)
            steptime.dispatch_end()
            if step == 0:
                _record_span("epoch.first_batch", *steptime.first_batch,
                             epoch=epoch,
                             ahead=self.train_loader.last_epoch_ahead)
                _record_span("epoch.first_dispatch",
                             *steptime.first_dispatch, epoch=epoch)
            self.last_epoch_steps = start_step + step + 1
            if (step + 1) % log_every == 0:
                handles = {"loss": metrics["loss"],
                           "accuracy": metrics["accuracy"]}
                if "lr" in metrics:
                    handles["lr"] = metrics["lr"]
                if "skip_count" in metrics:
                    # The in-graph consecutive-skip streak rides the SAME
                    # deferred drain as the other metrics — rollback
                    # detection costs zero extra host syncs.
                    handles["skip_count"] = metrics["skip_count"]
                # The family's counters (a looped model's exits, a routed
                # layer's pairs and load) ride the same drain.
                handles.update({k: v for k, v in metrics.items()
                                if k not in STEP_METRICS})
                _async_copy(handles)
                now = time.perf_counter()
                imgs_per_sec = log_every * global_batch / max(now - t_log,
                                                              1e-9)
                t_log = now
                if pending is not None:
                    self._drain_train_log(pending, losses, bar, epoch)
                pending = (step0 + step + 1, imgs_per_sec, handles)
                if step + 1 == remaining:
                    # Last step of the epoch: drain NOW, while the bar is
                    # still open (set_description on a closed bar is a
                    # no-op), so the final interval's loss is shown. The
                    # blocking read sits on the epoch boundary, off the
                    # steady-state path.
                    self._drain_train_log(pending, losses, bar, epoch)
                    pending = None
                if self._rollback_pending:
                    # Grinding out the rest of the epoch on (guarded but
                    # unprogressing) steps is pointless — hand back to
                    # fit() for the restore now.
                    bar.close()
                    break
            # Close the step's telemetry span (publishes the 'step'
            # event with the data/dispatch/device breakdown). Sits after
            # the deferred drain so blocking readbacks are charged to
            # the step that performed them.
            steptime.step_end(step0 + step + 1)
            if (self.cfg.run.max_steps
                    and step0 + step + 1 >= self.cfg.run.max_steps):
                # --steps budget (smoke runs / CI telemetry gate): stop
                # mid-epoch; fit() skips the epoch's val and exits.
                self._steps_exhausted = True
                bar.close()
                break
        with _span("epoch.tail", epoch=epoch):
            if pending is not None:
                # Post-loop drain (break paths: budget/rollback/preemption —
                # the in-loop last-step branch covers normal epoch ends): the
                # blocking readback here is the final dispatched step still
                # executing, i.e. device time AFTER its step event closed.
                # Published as a 'drain' span so the goodput ledger books it
                # as productive instead of losing it to 'other'.
                t_drain = time.perf_counter()
                self._drain_train_log(pending, losses, bar, epoch)
                _tm_publish("drain",
                            duration_s=round(time.perf_counter() - t_drain, 3))
            # Epoch-mean loss over all steps, one sync, off the hot path: the
            # running meter only sees logged points (display semantics identical
            # to the reference bar, train.py:67-68).
            if metrics is not None and losses.count == 0:
                losses.update(
                    float(metrics["loss"]), 1)  # tpuic-ok: TPU101 post-loop epoch boundary, one sync
            # Quarantine surfacing (docs/robustness.md): decode failures the
            # data layer absorbed this epoch, one console line + JSONL record
            # per epoch with events — a corrupt file is visible without being
            # fatal.
            q = self.train_loader.quarantine_count
            if q > self._quarantine_seen:
                delta = q - self._quarantine_seen
                self._quarantine_seen = q
                host0_print(f"[quarantine] epoch {epoch}: {delta} sample "
                            f"load(s) served a replacement (total {q})")
                self.logger.write(step0 + self.last_epoch_steps - start_step,
                                  quarantined=delta, quarantined_total=q)
            _tm_publish("epoch", epoch=epoch,
                        steps=self.last_epoch_steps - start_step,
                        loss=round(losses.avg, 6))
        return losses.avg

    def _drain_train_log(self, pending, losses: AverageMeter, bar,  # tpuic-ok: TPU101 THE deferred drain site
                         epoch: int) -> None:
        """Read one deferred log interval (a single batched device_get) and
        emit the bar description + JSONL record for it. Also the rollback
        watchdog: the drained skip_count is the in-graph consecutive
        non-finite streak; past run.skip_threshold it flags a rollback
        (detection latency <= ~2 log intervals — the price of keeping the
        hot path free of per-step host syncs)."""
        # the whole drain on the profiler's clock: the blocking read and
        # the log line it writes
        with _annotation("step.drain"):
            step_num, imgs_per_sec, handles = pending
            vals = jax.device_get(handles)
            loss = float(vals["loss"])
            losses.update(loss, 1)
            bar.set_description(
                f"Epoch: {epoch}; Loss {losses.val:.4f}|({losses.avg:.4f})")
            extra = {}
            streak = int(vals.get("skip_count", 0))
            if streak:
                extra["skipped_streak"] = streak
                # 'skip' event (docs/observability.md): the streak at this
                # drain plus the delta since the last one — the goodput
                # tracker charges that many steps to the skip bucket. At
                # log_every_steps=1 the delta is exact; at coarser cadences
                # it undercounts streaks that reset inside an interval
                # (documented estimate, same latency as rollback detection).
                last = getattr(self, "_last_skip_streak", 0)
                delta = streak - last if streak > last else streak
                _tm_publish("skip", step=step_num, streak=streak, delta=delta)
            self._last_skip_streak = streak
            counters = {k: float(v) for k, v in vals.items()
                        if k not in STEP_METRICS}
            if counters:
                # kept for the train_epoch span and the Prometheus rows
                self.last_counters = counters
                extra.update(counters)
            self.logger.write(step_num, loss=loss,
                              accuracy=float(vals["accuracy"]),
                              lr=float(vals.get("lr", 0.0)),
                              images_per_sec=round(imgs_per_sec, 1), **extra)
            thr = self.cfg.run.skip_threshold
            if (thr > 0 and streak >= thr and self.cfg.run.rollback
                    and not self._rollback_pending):
                host0_print(
                    f"[rollback] {streak} consecutive non-finite steps "
                    f"(threshold {thr}) at step {step_num} — state is still "
                    f"finite (guard skipped the updates); restoring the last "
                    f"good checkpoint instead of grinding forward")
                self._rollback_pending = True

    def val_epoch(self, epoch: int) -> float:
        """Reference val_epoch (train.py:78-97): exact global accuracy ×100,
        plus the exact global weighted val CE (num/den accumulated
        separately)."""
        with _span("val_epoch", epoch=epoch):
            return self._val_epoch(epoch)

    def _val_epoch(self, epoch: int) -> float:
        t_eval0 = time.perf_counter()
        correct = correct5 = count = loss_num = loss_den = 0.0
        have_top5 = False
        collect = self.cfg.run.collect_misclassified
        per_class = self.cfg.run.per_class_metrics
        confusion = None
        misclassified: list = []
        # Deferred accumulation: per-batch float() reads would serialize
        # every eval step against a device sync (the same stall the train
        # loop's deferred logging avoids), so metric handles are drained a
        # WINDOW behind dispatch. The window bound matters on the streaming
        # (non-resident) val path: each not-yet-executed step pins its uint8
        # batch upload in HBM, so unbounded run-ahead over a long val fold
        # would stack hundreds of ~20 MB buffers; draining handle i-W after
        # dispatching i throttles the host to at most W batches in flight.
        window = max(2, int(self.cfg.data.prefetch))
        pending: list = []

        def drain(m, indices) -> None:  # tpuic-ok: TPU101 deferred eval drain (window W behind dispatch)
            nonlocal correct, correct5, count, loss_num, loss_den, have_top5
            nonlocal confusion
            m = jax.device_get(m)
            correct += float(m["correct"])
            count += float(m["count"])
            loss_num += float(m["loss_num"])
            loss_den += float(m["loss_den"])
            if "correct5" in m:
                have_top5 = True
                correct5 += float(m["correct5"])
            if collect:
                # 'wrong' is the GLOBAL per-sample vector (replicated out of
                # the sharded step = all-gather over ICI); batch.indices is
                # the host-replicated global order — so every host can name
                # every misclassified sample, reference val_epoch's
                # all_gather capability (train.py:92) without the pickle.
                wrong = np.asarray(m["wrong"])
                ds = self.val_loader.dataset
                misclassified.extend(
                    ds.image_id(int(indices[pos]))
                    for pos in np.nonzero(wrong > 0.5)[0])
            if per_class:
                c = np.asarray(m["confusion"], np.float64)
                confusion = c if confusion is None else confusion + c
        for batch in self.val_loader.epoch(epoch):
            m = self.eval_step(self.state,
                               {k: batch[k] for k in ("image", "label", "mask")})
            _async_copy(m)
            pending.append((m, batch.indices if collect else None))
            if len(pending) > window:
                drain(*pending.pop(0))
        for item in pending:
            drain(*item)
        if collect:
            self.last_misclassified = misclassified
        score = 100.0 * correct / max(count, 1.0)
        val_loss = loss_num / max(loss_den, 1e-12)
        extra = {"n_misclassified": len(misclassified)} if collect else {}
        top5_msg = ""
        if have_top5:
            extra["val_top5"] = 100.0 * correct5 / max(count, 1.0)
            top5_msg = f"; Top-5 {extra['val_top5']:.4f}"
        if per_class and confusion is not None:
            # Exact global per-class accuracy: diagonal / true-class counts.
            # Scalars (balanced = mean per-class recall, and the worst
            # class) ride the normal logger; the full vector + confusion
            # matrix are non-scalar, so they go to sidecar files beside
            # metrics.jsonl.
            support = confusion.sum(axis=1)
            cls_acc = np.divide(np.diag(confusion), support,
                                out=np.zeros_like(support),
                                where=support > 0)
            present = support > 0
            if present.any():
                extra["val_balanced_acc"] = 100.0 * cls_acc[present].mean()
                extra["val_worst_class_acc"] = 100.0 * cls_acc[present].min()
            if self.logger.root is not None:
                # Per-epoch file: the off-diagonal structure at (say) the
                # best-checkpoint epoch must survive later epochs.
                np.save(os.path.join(self.logger.root,
                                     f"confusion_e{epoch}.npy"), confusion)
                with open(os.path.join(self.logger.root,
                                       "per_class.jsonl"), "a") as f:
                    f.write(json.dumps({
                        "epoch": epoch,
                        "acc": [round(100.0 * a, 2) for a in cls_acc],
                        "support": [int(s) for s in support]}) + "\n")
        host0_print(f"Epoch: {epoch}; Val Accuracy {score:.4f}{top5_msg}; "
                    f"Val Loss {val_loss:.4f}")
        self.logger.write(int(jax.device_get(self.state.step)),  # tpuic-ok: TPU101 epoch boundary
                          val_accuracy=score, val_loss=val_loss, **extra)
        _tm_publish("eval", epoch=epoch, accuracy=round(score, 4),
                    duration_s=round(time.perf_counter() - t_eval0, 3))
        return score

    # -- driver -------------------------------------------------------------
    def _do_rollback(self) -> int:
        """Restore the last good checkpoint after a non-finite streak
        (docs/robustness.md); returns the epoch to continue from.

        The restore goes through the integrity ladder, the skip streak is
        reset, and with run.rollback_rewarm_steps the LR re-enters its
        schedule on a linear ramp (a new optimizer transform — one retrace
        of the train step, the only recompile on any rollback path)."""
        self._rollback_pending = False
        self.rollbacks += 1
        t_rb0 = time.perf_counter()
        run = self.cfg.run
        if self.rollbacks > run.max_rollbacks:
            # NonRetryable: a supervisor restart would resume, diverge,
            # and land right back here — the poison half of the
            # exit-code contract (runtime/supervisor.py).
            from tpuic.runtime.supervisor import NonRetryableError
            raise NonRetryableError(
                f"non-finite rollback #{self.rollbacks} exceeds "
                f"run.max_rollbacks={run.max_rollbacks}: the run keeps "
                "diverging after restore — fix the data/LR instead of "
                "looping restore->diverge forever")
        # Commit any staged save FIRST: the most recent epoch's checkpoint
        # normally still sits in '{track}.new' (its commit rides the next
        # wait()), and probing newest_track() before committing would
        # spuriously report "nothing to roll back to".
        self.ckpt.wait()
        if self.ckpt.newest_track() is None:
            from tpuic.runtime.supervisor import NonRetryableError
            raise NonRetryableError(
                f"{run.skip_threshold} consecutive non-finite steps before "
                "any checkpoint existed — nothing to roll back to (the "
                "guard kept the state finite; lower the LR or check the "
                "data)")
        import jax.numpy as jnp
        self.state, epoch, restored_best = self.ckpt.restore_into(self.state)
        # 'best' on disk still holds its score; never let a rollback
        # resurrect a worse-looking history.
        self.best_score = max(self.best_score, restored_best)
        self.state = self.state.replace(skip_count=jnp.zeros((), jnp.int32))
        if run.rollback_rewarm_steps > 0:
            from tpuic.train.optimizer import make_optimizer, rewarm_scale
            steps = max(1, self.train_loader.steps_per_epoch())
            base_step = int(np.asarray(jax.device_get(self.state.step)))  # tpuic-ok: TPU101 rollback path, not steady state
            scale = rewarm_scale(base_step, run.rollback_rewarm_steps)
            self.state = self.state.replace(tx=make_optimizer(
                self.cfg.optim, steps, run.epochs, lr_scale=scale,
                global_batch=self.train_loader.global_batch))
            # The logged 'lr' metric must report what the optimizer now
            # APPLIES: fold the ramp into the metric schedule and rebuild
            # the step around it (one retrace — the same one the new tx
            # forces anyway). Composed onto the PRISTINE base schedule —
            # the optimizer rebuild above applies only the newest scale,
            # so stacking onto an already-scaled self.schedule (rollback
            # #2 inside rollback #1's ramp) would under-report the LR.
            from tpuic.train.optimizer import make_schedule
            base_sched = make_schedule(
                self.cfg.optim, steps, run.epochs,
                global_batch=self.train_loader.global_batch)
            self.schedule = lambda t: base_sched(t) * scale(t)
            self.train_step = make_train_step(
                self.cfg.optim, self.mcfg,
                self.mesh if self.mesh.size > 1 else None,
                lr_schedule=self.schedule, seed=self.cfg.run.seed,
                state_sharding=self.state_sharding)
            host0_print(f"[rollback] LR re-warming over "
                        f"{run.rollback_rewarm_steps} steps from step "
                        f"{base_step}")
        if self.state_sharding is not None:
            from tpuic.parallel.sharding import shard_state
            self.state = shard_state(self.state, self.state_sharding)
        self.start_epoch = epoch
        self.start_step = self._validated_start_step()
        host0_print(f"[rollback] restored '{self.ckpt.last_restore_rung}' — "
                    f"continuing at epoch {epoch} step {self.start_step} "
                    f"(rollback {self.rollbacks}/{run.max_rollbacks})")
        self._last_skip_streak = 0
        _tm_publish("rollback", epoch=epoch, rollback=self.rollbacks,
                    rung=self.ckpt.last_restore_rung,
                    duration_s=round(time.perf_counter() - t_rb0, 3))
        return epoch

    def _rebuild_for_replicas(self, replicas: int) -> None:
        """Re-form the in-process compute plane at a new data-parallel
        extent — the "recompile, don't respawn" half of elastic
        membership (docs/parallelism.md): a fresh mesh over the first
        ``replicas`` replica slots (runtime/mesh.py ``replica_mesh``),
        loaders re-sliced to the new global batch, schedule/optimizer
        rebuilt in the new step time (the batch-scaled LR rule sees the
        new global batch), state resharded onto the new mesh, and the
        step functions re-jitted. Only reached when this process owns a
        multi-replica mesh; an independent-rank fleet (mesh.size == 1)
        has no local mesh to shrink and re-forms state only."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tpuic.runtime.mesh import replica_mesh
        cfg = self.cfg
        self.mesh = replica_mesh(replicas, cfg.mesh)
        step_mesh = self.mesh if self.mesh.size > 1 else None
        self._close_loaders()
        global_batch = self._build_loaders()
        steps = max(1, self.train_loader.steps_per_epoch())
        self.schedule = make_schedule(cfg.optim, steps, cfg.run.epochs,
                                      global_batch=global_batch)
        self.state = self.state.replace(
            tx=make_optimizer(cfg.optim, steps, cfg.run.epochs,
                              global_batch=global_batch))
        self.state_sharding = None
        if step_mesh is not None and (cfg.mesh.fsdp or cfg.mesh.zero1 or (
                cfg.mesh.tensor_parallel and self.mesh.shape["model"] > 1)):
            from tpuic.parallel.sharding import shard_state, state_shardings
            self.state_sharding = state_shardings(
                self.state, self.mesh, tp=cfg.mesh.tensor_parallel,
                fsdp=cfg.mesh.fsdp, zero1=cfg.mesh.zero1)
            self.state = shard_state(self.state, self.state_sharding)
        else:
            # Replicated state must MOVE onto the shrunken mesh before
            # the re-jitted step sees it: a leaf still laid out over the
            # old R-device mesh fails the new program's device
            # assignment instead of resharding silently.
            repl = NamedSharding(self.mesh, P())
            self.state = jax.tree.map(
                lambda x: jax.device_put(x, repl), self.state)
        self._build_steps()

    def _do_reform(self, m) -> int:
        """Act on a 'degrade' membership transition (docs/parallelism.md
        "Elastic data parallelism"): shrink the local mesh if this
        process owns one, then restore the fleet-agreed step through the
        capped integrity ladder — all in-process (the pid is the proof;
        the elastic soak pins it). Returns the epoch to continue from."""
        self._reform_pending = None
        self.reforms += 1
        t0 = time.perf_counter()
        # Commit any staged save first (the rollback discipline): the
        # capped ladder must see every rung that exists.
        self.ckpt.wait()
        # Shrink the LOCAL mesh only when it IS the fleet (one process
        # hosting all m.world replicas — rank ids map 1:1 onto replica
        # slots, so "R' survivors" means "R' local replicas"). A
        # multi-host rank whose local mesh spans several replicas can't
        # equate fleet rank count with local extent (and which slots
        # survived isn't local knowledge): there the membership/restore
        # half applies and the mesh change rides the collective
        # re-initialization (docs/parallelism.md, CPU-fleet caveat).
        if (self.mesh.shape["data"] > 1
                and m.world == self.mesh.shape["data"]
                and 0 < len(m.active) < self.mesh.shape["data"]):
            self._rebuild_for_replicas(len(m.active))
        self.state, epoch, restored_best = self.ckpt.restore_into(
            self.state, resume_cap=m.resume_step)
        self.best_score = max(self.best_score, restored_best)
        if self.state.skip_count is not None:
            import jax.numpy as jnp
            self.state = self.state.replace(
                skip_count=jnp.zeros((), jnp.int32))
        if self.state_sharding is not None:
            from tpuic.parallel.sharding import shard_state
            self.state = shard_state(self.state, self.state_sharding)
        self.start_epoch = epoch
        self.start_step = self._validated_start_step()
        self._last_skip_streak = 0
        what = (f"fleet degraded to {len(m.active)}/{m.world} "
                f"(rank {m.rank} lost)" if m.reason == "degrade"
                else f"coalesced '{m.reason}' transition (a degrade came "
                     f"and went between polls; fleet at "
                     f"{len(m.active)}/{m.world})")
        host0_print(
            f"[elastic] membership v{m.version}: {what} — re-formed "
            f"in place from fleet-agreed step {m.resume_step} (rung "
            f"'{self.ckpt.last_restore_rung}'); continuing at epoch "
            f"{epoch} step {self.start_step}, no process restart")
        _tm_publish("reform", reason=m.reason, version=m.version,
                    active=list(m.active), resume_step=m.resume_step,
                    acted=True, epoch=epoch, rung=self.ckpt.last_restore_rung,
                    duration_s=round(time.perf_counter() - t0, 3))
        return epoch

    def fit(self, epochs: Optional[int] = None) -> float:
        from tpuic.runtime.preemption import agree
        epochs = epochs if epochs is not None else self.cfg.run.epochs
        best = self.best_score
        profiled = False
        if self.cfg.run.handle_preemption:
            self.preemption.install()
        goodput = self.telemetry.goodput
        goodput.start()
        # Supervised restart (runtime/supervisor.py): announce it as a
        # typed event. The downtime — previous child's death through
        # backoff, respawn, re-init, and checkpoint restore to here — is
        # charged to the goodput 'restart' bucket, so post-restart wall
        # time is classified instead of vanishing into 'other'.
        from tpuic.runtime.supervisor import restart_info
        rinfo = restart_info()
        if rinfo is not None:
            count, downtime_s = rinfo
            host0_print(f"[supervise] restart #{count}: resumed at epoch "
                        f"{self.start_epoch} step {self.start_step} after "
                        f"{downtime_s:.1f}s downtime")
            _tm_publish("restart", restart=count,
                        downtime_s=round(downtime_s, 3),
                        epoch=self.start_epoch, step_in_epoch=self.start_step)
        # Manifest-driven restart prewarm (docs/performance.md,
        # "Compiled-program registry"): when TPUIC_COMPILE_MANIFEST
        # names a manifest a PREVIOUS life left behind, compile-and-run
        # every step program now — against the persistent XLA cache —
        # so the steady state below dispatches with zero compiles.  A
        # corrupt manifest is refused loudly and training proceeds
        # unwarmed (correctness never depended on the prewarm).
        mpath = os.environ.get("TPUIC_COMPILE_MANIFEST", "")
        if mpath and getattr(self, "_manifest_preexisting", False):
            from tpuic.compiled import ManifestError
            try:
                self.prewarm(mpath)
            except ManifestError as e:
                host0_print(f"[compiled] refusing prewarm manifest: {e}")
            except FileNotFoundError:
                pass
        self._steps_exhausted = False
        try:
            epoch = self.start_epoch
            while epoch < epochs:
                if (self.cfg.run.profile_dir and not profiled
                        and epoch == self.start_epoch):
                    jax.profiler.start_trace(self.cfg.run.profile_dir)
                    profiled = True
                t0 = time.time()
                self.train_epoch(
                    epoch,
                    self.start_step if epoch == self.start_epoch else 0)
                if self._rollback_pending:
                    # Non-finite streak past skip_threshold: restore the
                    # last good checkpoint and continue from ITS epoch.
                    if profiled:
                        jax.profiler.stop_trace()
                        profiled = False
                    epoch = self._do_rollback()
                    best = self.best_score
                    continue
                if self._reform_pending is not None:
                    # Elastic degrade: a peer died; re-form in place from
                    # the fleet-agreed step and continue from ITS epoch.
                    if profiled:
                        jax.profiler.stop_trace()
                        profiled = False
                    epoch = self._do_reform(self._reform_pending)
                    best = self.best_score
                    continue
                if self._steps_exhausted:
                    # --steps budget reached mid-epoch: a smoke run's
                    # contract is N train steps + a goodput report, not
                    # a val pass over an unfinished epoch.
                    host0_print(f"[tpuic] step budget "
                                f"({self.cfg.run.max_steps}) reached in "
                                f"epoch {epoch}; stopping")
                    break
                # Epoch end is a common boundary: agree so a host whose
                # local SIGTERM missed the last in-epoch sync point doesn't
                # diverge from the others (val vs flush).
                if (self.cfg.run.handle_preemption
                        and agree(self.preemption.triggered)):
                    self.preemption.trigger()
                    if profiled:
                        jax.profiler.stop_trace()
                        profiled = False
                    # Grace windows are short: skip val and flush 'latest'.
                    # The save carries the completed step count so resume
                    # continues the epoch exactly where it stopped (no
                    # replayed prefix, no skipped tail). A boundary flush
                    # (done == total) records the full count: resume then
                    # trains ZERO remaining steps and runs the epoch's
                    # still-pending validation — so val/save_best are never
                    # lost to a signal landing between train and val.
                    done = self.last_epoch_steps
                    total = len(self.train_loader)
                    host0_print(f"[preempt] signal received during epoch "
                                f"{epoch} (step {done}/{total}); flushing "
                                f"latest and exiting")
                    gb, seed, n = self._loader_geometry()
                    self.ckpt.save_latest(
                        self.state, epoch, best, step_in_epoch=done,
                        global_batch=gb, data_seed=seed, data_len=n)
                    break
                score = self.val_epoch(epoch)
                host0_print(f"Epoch {epoch} took {time.time() - t0:.1f}s")
                if profiled:
                    jax.profiler.stop_trace()
                    profiled = False
                if score > best:
                    best = score
                    self.ckpt.save_best(self.state, epoch, best)
                self.ckpt.maybe_save_latest(self.state, epoch, best)
                # Epoch-cadence goodput: one console line plus a
                # 'goodput' event (TensorBoard fractions via the bus
                # sink, JSONL via --metrics-jsonl).
                host0_print(f"[goodput] {goodput.summary_line()}")
                _tm_publish("goodput", step=self.telemetry.steptime.last_step,
                            **goodput.report())
                epoch += 1
        finally:
            self.preemption.uninstall()
            self._close_loaders()
            # Commit any staged save on EVERY exit path: an exception
            # during epoch N+1 must not strand epoch N's fully-written
            # checkpoint in '{track}.new' (the restore ladder only reads
            # committed tracks).
            self.ckpt.wait()
            if self.telemetry.tracer is not None:
                self.telemetry.tracer.finish()
            if self.telemetry.profile is not None:
                # Final device-time analysis BEFORE the final goodput
                # event: the goodput publish drives the --prom-dump
                # refresh, which must see the finished waterfall
                # (finalize is idempotent; flush() backstops it).
                self.telemetry.profile.finalize()
            # Final goodput report — the run's wall-time ledger
            # (productive/input/compile/checkpoint/skip/rollback/eval;
            # CI asserts the buckets sum to ~100% of wall).
            host0_print(f"[goodput] {goodput.summary_line()}")
            _tm_publish("goodput", final=True,
                        step=self.telemetry.steptime.last_step,
                        **goodput.report())
            self.telemetry.flush()
        self.best_score = best
        return best
