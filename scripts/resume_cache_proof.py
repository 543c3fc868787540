#!/usr/bin/env python
"""Chip proof: preemption resume composes with the device-resident cache.

VERDICT r4 weak #5 / item 6: step-exact resume is CPU-verified
(tests/test_preemption.py), but its interaction with the device-resident
dataset cache — resume mid-epoch => re-upload, stride replay — had never
run on a real chip, and the resident path is the production default on
TPU. This script runs, ON THE CURRENT PLATFORM:

  control      = Trainer.fit(2 epochs), digits ImageFolder, resident cache
  interrupted  = same config, preemption latch tripped mid-epoch-1
                 (the SIGTERM latch, triggered in-process), flush, then a
                 fresh Trainer resumes and finishes

and asserts (a) the resident cache was actually active in every run,
(b) resume re-entered the interrupted epoch at the recorded step, (c) the
final params match the control (bitwise reported, allclose asserted), and
(d) the resumed loop logged steady throughput. Writes
perf/resume_cache_proof.json.

PR-18 extension (compiled-program registry, docs/performance.md): the
resume is run TWICE from byte-identical checkpoints — arm A cold (no
prewarm: the first fit step pays the train-step compile in the training
line) and arm B prewarmed from the manifest the interrupted run wrote
(Trainer.prewarm compiles+executes every manifest-listed program before
the loop; the fit itself must then be compile-flat, checker-asserted).
The registry is reset() between arms to simulate the cold process a real
restart is.  The JSON gains the prewarm-vs-no-prewarm downtime split.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

OUT = os.path.join(_REPO, "perf", "resume_cache_proof.json")


def main() -> None:
    import jax

    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np

    from scripts.convergence_digits import ensure_dataset, DATA_ROOT
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.train.loop import Trainer

    ensure_dataset()
    on_cpu = jax.devices()[0].platform == "cpu"
    work = tempfile.mkdtemp(prefix="tpuic_resume_cache_")

    def cfg(ckpt):
        return Config(
            data=DataConfig(data_dir=DATA_ROOT, resize_size=32,
                            batch_size=128, augment=False,
                            device_cache_mb=4096),
            model=ModelConfig(name="resnet18-cifar", num_classes=10,
                              dtype="float32" if on_cpu else "bfloat16"),
            optim=OptimConfig(optimizer="sgd", learning_rate=0.05,
                              warmup_epochs=1, class_weights=(),
                              milestones=()),
            run=RunConfig(epochs=2, ckpt_dir=ckpt, save_period=100,
                          resume=True, log_every_steps=2),
            mesh=MeshConfig(),
        )

    def trip_after(trainer, n_steps):
        orig, calls = trainer.train_step, []

        def counting_step(state, batch):
            out = orig(state, batch)
            calls.append(1)
            if len(calls) == n_steps:
                trainer.preemption.trigger()
            return out

        trainer.train_step = counting_step
        return calls

    def first_step_probe(trainer):
        """Stamp the wall time the first train step COMPLETES — the
        time-to-first-step split between the two resume arms."""
        orig, box = trainer.train_step, {}

        def probing(state, batch):
            out = orig(state, batch)
            box.setdefault("t", time.perf_counter())
            return out

        trainer.train_step = probing
        return box

    t0 = time.perf_counter()
    control = Trainer(cfg(os.path.join(work, "ck_a")),
                      log_dir=os.path.join(work, "log_a"))
    assert control.train_loader.resident, \
        "resident cache did not engage — the proof target is the resident path"
    steps_per_epoch = control.train_loader.steps_per_epoch()
    control.fit()
    control_s = time.perf_counter() - t0

    # The interrupted run writes the prewarm manifest (the registry's
    # _build_steps hook) — exactly what a production gang member leaves
    # behind for its restarted self.
    manifest = os.path.join(work, "programs.manifest.json")
    os.environ["TPUIC_COMPILE_MANIFEST"] = manifest
    try:
        trip_offset = max(1, steps_per_epoch // 2)
        interrupted = Trainer(cfg(os.path.join(work, "ck_b")),
                              log_dir=os.path.join(work, "log_b"))
        assert interrupted.train_loader.resident
        trip_after(interrupted, steps_per_epoch + trip_offset)
        interrupted.fit()
    finally:
        del os.environ["TPUIC_COMPILE_MANIFEST"]
    assert os.path.exists(manifest), "interrupted run left no manifest"

    # Two resume arms from byte-identical interrupted checkpoints.
    import shutil
    shutil.copytree(os.path.join(work, "ck_b"), os.path.join(work, "ck_b2"))

    from tpuic.analysis.runtime import watch_compiles
    from tpuic.compiled import registry

    def resume_arm(ckpt, log, *, prewarm_manifest=None):
        registry.reset()  # a restart is a cold process: no in-proc reuse
        t1 = time.perf_counter()
        trainer = Trainer(cfg(os.path.join(work, ckpt)),
                          log_dir=os.path.join(work, log))
        assert trainer.train_loader.resident
        assert (trainer.start_epoch, trainer.start_step) == \
            (1, trip_offset), (
                f"resume geometry: expected (1, {trip_offset}), got "
                f"{(trainer.start_epoch, trainer.start_step)}")
        pw = (trainer.prewarm(prewarm_manifest)
              if prewarm_manifest else None)
        t_ready = time.perf_counter()
        probe = first_step_probe(trainer)
        with watch_compiles() as w:
            trainer.fit()
        return {"trainer": trainer, "prewarm": pw,
                "fit_compiles": w.compiles,
                "total_s": time.perf_counter() - t1,
                "first_step_s": probe["t"] - t_ready}

    arm_a = resume_arm("ck_b", "log_b")                       # no prewarm
    arm_b = resume_arm("ck_b2", "log_b2", prewarm_manifest=manifest)
    assert arm_b["fit_compiles"] == 0, (
        f"manifest-prewarmed resume was NOT compile-flat: "
        f"{arm_b['fit_compiles']} backend compile(s) inside fit")
    resumed = arm_a["trainer"]
    resume_s = arm_a["total_s"]

    a = jax.device_get(control.state.params)
    b = jax.device_get(resumed.state.params)
    b2 = jax.device_get(arm_b["trainer"].state.params)
    leaves = list(zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)))
    bitwise = all(np.array_equal(np.asarray(x), np.asarray(y))
                  for x, y in leaves)
    # Prewarm executes the step on a copied state against a throwaway
    # batch — it must not perturb the resumed trajectory by one bit.
    prewarm_bitwise = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(b),
                        jax.tree_util.tree_leaves(b2)))
    max_diff = max(float(np.max(np.abs(np.asarray(x, np.float32)
                                       - np.asarray(y, np.float32))))
                   for x, y in leaves)

    rates = []
    with open(os.path.join(work, "log_b", "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "images_per_sec" in rec:
                rates.append(rec["images_per_sec"])

    result = {
        "platform": jax.devices()[0].platform,
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        "dataset": "digits ImageFolder (real data, resident cache)",
        "resident_bytes": control.train_loader.resident_bytes,
        "steps_per_epoch": steps_per_epoch,
        "trip": f"epoch 1 step {trip_offset}",
        "resume_geometry_ok": True,
        "params_bitwise_equal": bool(bitwise),
        "params_max_abs_diff": max_diff,
        # metrics.jsonl of ck_b spans both runs: the pre-interrupt epoch's
        # intervals first, then the resumed run's (the steady-rate
        # evidence is the tail).
        "interrupted_plus_resumed_rates": rates,
        "control_fit_s": round(control_s, 1),
        "resume_fit_s": round(resume_s, 1),
        # Prewarm-vs-no-prewarm downtime split (compiled-program
        # registry, docs/performance.md): arm A pays its compiles at the
        # first step of the training line; arm B pays them in
        # Trainer.prewarm before the loop and its fit is compile-flat.
        "resume_prewarm_fit_s": round(arm_b["total_s"], 1),
        "prewarm_s": round(arm_b["prewarm"]["prewarm_s"], 2),
        "prewarm_programs": arm_b["prewarm"]["programs"],
        "prewarm_manifest_listed": arm_b["prewarm"]["manifest_listed"],
        "first_step_s_no_prewarm": round(arm_a["first_step_s"], 2),
        "first_step_s_after_prewarm": round(arm_b["first_step_s"], 2),
        "fit_compiles_no_prewarm": arm_a["fit_compiles"],
        "fit_compiles_after_prewarm": arm_b["fit_compiles"],
        "prewarm_params_bitwise_equal": bool(prewarm_bitwise),
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    assert max_diff == 0.0 or max_diff < 1e-6, \
        f"resumed params diverge from control by {max_diff}"
    assert prewarm_bitwise, \
        "prewarmed resume diverged from the cold resume (prewarm leaked " \
        "into trainer state or loader position)"
    print("RESUME CACHE PROOF OK")


if __name__ == "__main__":
    main()
