#!/usr/bin/env python
"""CLI entry point — the TPU-native counterpart of reference train.py.

The reference is launched as
``python -m torch.distributed.launch --nproc_pre_node=4 train.py --datadir …``
(reference README.md:6) with three flags (train.py:27-31). Here a single
process per host drives all local TPU chips; multi-host pods need no launcher
flags at all (the TPU runtime carries the topology — tpuic/runtime/
distributed.py). Every constant the reference hard-codes is a flag with the
same default (see tpuic/config.py for the line-by-line mapping).

Examples:
  python train.py --datadir /data/imagefolder                 # reference defaults
  python train.py --datadir /data/cifar --model resnet18-cifar \
      --resize 32 --batchsize 128 --lr 1e-3 --no-class-weights
"""

from __future__ import annotations

import argparse
import dataclasses

from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                          OptimConfig, RunConfig)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    # The reference's three flags (train.py:27-31).
    p.add_argument("--datadir", required=True, help="ImageFolder root with train/ and val/")
    p.add_argument("--batchsize", type=int, default=4,
                   help="per-device batch size (reference default 4)")
    p.add_argument("--local_rank", type=int, default=0,
                   help="accepted for launch-command compatibility; unused — "
                        "one JAX process drives all local chips")
    # Everything the reference hard-codes (train.py:110-183).
    p.add_argument("--model", default="inceptionv3",
                   help="backbone name (see tpuic.models.available_models()); "
                        "default matches the reference's hard-coded "
                        "'inceptionv3' (train.py:122). The perf-tracking "
                        "config (BASELINE.md) uses --model resnet50.")
    p.add_argument("--num-classes", type=int, default=0,
                   help="0 = infer from the folder tree")
    p.add_argument("--resize", type=int, default=299)
    p.add_argument("--epochs", type=int, default=100)
    p.add_argument("--lr", type=float, default=0.5e-5)
    p.add_argument("--optimizer", default="adam",
                   choices=["adam", "lars", "lamb", "sgd"],
                   help="'lars'/'lamb' are the layer-wise trust-ratio "
                        "large-batch optimizers (arXiv:1708.03888 / "
                        "1904.00962); pair them with --base-batch for "
                        "the linear-scaling warmup")
    p.add_argument("--milestones", type=int, nargs="*", default=[50, 80])
    p.add_argument("--gamma", type=float, default=0.5)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--clip-grad-norm", type=float, default=0.0,
                   help="clip gradients to this global L2 norm before the "
                        "optimizer update (0 = off; standard in ViT/large-"
                        "batch recipes)")
    p.add_argument("--mixup", type=float, default=0.0, metavar="ALPHA",
                   help="mixup Beta(alpha, alpha) image/label mixing, "
                        "applied on-device in the train step (0 = off)")
    p.add_argument("--cutmix", type=float, default=0.0, metavar="ALPHA",
                   help="cutmix box mixing, on-device (0 = off; with "
                        "--mixup, one is chosen per step 50/50)")
    p.add_argument("--random-erase", type=float, default=0.0, metavar="P",
                   help="per-sample probability of erasing a random box "
                        "on-device in the train step (0 = off)")
    p.add_argument("--warmup-epochs", type=int, default=0)
    p.add_argument("--base-batch", type=int, default=0, metavar="N",
                   help="Goyal linear-scaling rule: peak LR = --lr * "
                        "global_batch / N, reached by a linear warmup "
                        "from --lr over --warmup-epochs (0 = off). The "
                        "global batch tracks the data-parallel extent, "
                        "so one config survives fleet growth and "
                        "elastic degrade alike")
    p.add_argument("--grad-accum-steps", type=int, default=1,
                   help="accumulate gradients over K steps before one "
                        "optimizer update (effective batch = K * global)")
    p.add_argument("--class-weights", type=str, nargs="*",
                   default=["3", "3", "10", "1", "4", "4", "5"],
                   help="CE class weights (reference train.py:157), or the "
                        "single word 'auto' to derive inverse-frequency "
                        "weights from the train fold's class counts")
    p.add_argument("--no-class-weights", action="store_true")
    p.add_argument("--ckpt-dir", default="dtmodel/cp")
    p.add_argument("--save-period", type=int, default=5)
    p.add_argument("--no-resume", action="store_true")
    p.add_argument("--init-from", default="",
                   help="initialize from a torch checkpoint (reference "
                        "best_model/latest_model file or a torchvision/"
                        "efficientnet_pytorch state_dict); backbone family "
                        "is auto-detected and weights merge leniently")
    p.add_argument("--workers", type=int, default=6)
    p.add_argument("--val-batchsize", type=int, default=0,
                   help="per-device val batch (0 = same as --batchsize; the "
                        "reference pins 1, train.py:118 — only needed there "
                        "for its per-sample gather)")
    p.add_argument("--prefetch", type=int, default=2,
                   help="host-side prefetch depth (batches in flight)")
    p.add_argument("--device-cache-mb", type=int, default=4096,
                   help="HBM budget for the device-resident dataset cache "
                        "(0 disables; see docs/performance.md)")
    p.add_argument("--log-every-steps", type=int, default=50,
                   help="metric readback cadence; 1 = reference-style "
                        "per-step logging (serializes dispatch)")
    p.add_argument("--label-smoothing", type=float, default=0.0)
    p.add_argument("--ema-decay", type=float, default=0.0,
                   help="EMA of params (0 off; typical 0.9999); validation "
                        "and best-checkpoint selection use EMA weights")
    p.add_argument("--freeze-backbone", action="store_true",
                   help="train only the MLP head (pairs with --init-from); "
                        "gradient-level freeze, BN stats still update")
    p.add_argument("--fused-loss", action="store_true",
                   help="use the Pallas fused weighted-CE kernel "
                        "(tpuic/kernels/cross_entropy.py)")
    p.add_argument("--no-augment", action="store_true",
                   help="disable the train-fold rot90/flip/jitter chain "
                        "(orientation-sensitive datasets, e.g. digits); "
                        "normalization and val behavior are unchanged")
    p.add_argument("--no-native", action="store_true",
                   help="disable the native C++ decode/prep core "
                        "(tpuic/native) and run the pure-NumPy input "
                        "path — the parity reference the native "
                        "kernels are pinned against")
    p.add_argument("--no-pack", action="store_true",
                   help="disable the packed uint8 cache + device-side "
                        "augmentation; decode every epoch like the reference")
    p.add_argument("--cache-dir", default="",
                   help="packed-cache dir (default {datadir}/.tpuic_pack)")
    p.add_argument("--collect-misclassified", action="store_true",
                   help="gather misclassified val image ids each epoch "
                        "(the reference's per-sample all_gather capability)")
    p.add_argument("--per-class-metrics", action="store_true",
                   help="log exact global per-class val accuracy and save "
                        "the [C,C] confusion matrix beside metrics.jsonl")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--compute-dtype", default="", dest="compute_dtype",
                   choices=["", "bf16", "f32"],
                   help="training compute-dtype policy: 'bf16' runs "
                        "forward/backward in bfloat16 with f32 master "
                        "weights, f32 optimizer moments and f32 "
                        "checkpoints (the mixed-precision tier, parity-"
                        "gated in CI); 'f32' forces full float32 (the "
                        "parity reference arm); '' defers to --dtype")
    p.add_argument("--loss-scale", type=float, default=1.0,
                   help="static loss scaling for --compute-dtype bf16 "
                        "(loss x N before backward, grads / N after; "
                        "1.0 = off — bf16 with f32 master weights "
                        "rarely needs it; overflow rides the skip "
                        "guard)")
    p.add_argument("--fused-optimizer", action="store_true",
                   help="use the fused one-pass Pallas optimizer-update "
                        "kernel for lars/lamb "
                        "(tpuic/kernels/optimizer_update.py; jnp "
                        "fallback off-TPU)")
    p.add_argument("--no-async-checkpoint", action="store_true",
                   help="commit checkpoints synchronously (block the "
                        "step timeline on manifest + rotation) instead "
                        "of on the background commit thread")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model-axis", type=int, default=1,
                   help="mesh model-axis size (1 = pure data parallel; >1 = "
                        "Megatron tensor parallelism from the models' "
                        "logical axis annotations)")
    p.add_argument("--seq-axis", type=int, default=1,
                   help="mesh seq-axis size for sequence-parallel attention "
                        "(ring/ulysses; attention-bearing backbones only)")
    p.add_argument("--fsdp", action="store_true",
                   help="shard params + optimizer moments over the data axis "
                        "(ZeRO-3 semantics)")
    p.add_argument("--zero1", action="store_true",
                   help="shard ONLY the optimizer moments over the data axis "
                        "(weight-update sharding: params stay replicated, "
                        "1/N Adam memory; subsumed by --fsdp)")
    from tpuic.models import ATTENTION_IMPLS
    p.add_argument("--attention", default="dense",
                   choices=list(ATTENTION_IMPLS),
                   help="attention implementation for ViT backbones")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize the forward in backward (trade FLOPs "
                        "for activation memory/bandwidth)")
    p.add_argument("--remat-policy", default="dots",
                   choices=["dots", "attention", "blocks", "gelu"],
                   help="what --remat saves: 'dots' recomputes all "
                        "activation-sized tensors; 'attention' recomputes "
                        "ONLY the [B,H,N,N] attention logits/probs (ViT); "
                        "'blocks' saves only the block inputs (ViT "
                        "long-context memory mode; the looped ouro-* "
                        "stack's memory mode); 'gelu' drops only the "
                        "ViT MLP pre-activations (lightest — one fewer "
                        "[B,N,4D] HBM write/read per block)")
    p.add_argument("--drop-path", type=float, default=0.0,
                   help="stochastic-depth rate for ViT backbones (last "
                        "block; linear DeiT ramp from 0)")
    p.add_argument("--bn-bf16-stats", action="store_true",
                   help="accumulate BatchNorm batch statistics in bf16 "
                        "instead of f32 (ResNet family; HBM-bandwidth "
                        "experiment — see ModelConfig.bn_f32_stats)")
    p.add_argument("--profile-dir", default="",
                   help="write a jax.profiler trace of the first epoch here")
    p.add_argument("--log-dir", default="", help="metrics.jsonl directory")
    p.add_argument("--no-skip-guard", action="store_true",
                   help="disable the in-graph non-finite step guard (a "
                        "NaN/Inf batch then poisons the optimizer state "
                        "permanently — see docs/robustness.md)")
    p.add_argument("--skip-threshold", type=int, default=10,
                   help="consecutive non-finite (skipped) steps before the "
                        "trainer rolls back to the last good checkpoint "
                        "(0 disables detection)")
    p.add_argument("--no-rollback", action="store_true",
                   help="never roll back on a non-finite streak (keep "
                        "skipping instead)")
    p.add_argument("--rewarm-steps", type=int, default=0,
                   help="after a rollback, ramp the LR linearly back to "
                        "its schedule over this many steps (0 = resume at "
                        "full schedule LR)")
    p.add_argument("--no-quarantine", action="store_true",
                   help="fail fast on undecodable images instead of "
                        "serving a deterministic same-class replacement")
    # Telemetry (tpuic/telemetry, docs/observability.md).
    p.add_argument("--steps", type=int, default=0,
                   help="stop after this many optimizer steps regardless "
                        "of --epochs (0 = no cap; smoke runs and the CI "
                        "telemetry gate use it)")
    p.add_argument("--metrics-jsonl", default="",
                   help="telemetry event JSONL sink: per-step time "
                        "breakdown, skip/rollback/quarantine/checkpoint/"
                        "compile events, and the final goodput report")
    p.add_argument("--trace-dir", default="",
                   help="triggered jax.profiler traces land here when a "
                        "step regresses past --trace-threshold x the "
                        "rolling median (TPUIC_TRACE=dir forces one "
                        "immediate window)")
    p.add_argument("--trace-threshold", type=float, default=3.0,
                   help="step-time regression multiple that arms a trace "
                        "(0 disables the automatic trigger)")
    p.add_argument("--trace-steps", type=int, default=3,
                   help="steps each triggered trace window covers")
    p.add_argument("--trace-analyze", action="store_true",
                   help="auto-analyze captured trace windows (and the "
                        "full run at exit) into a per-op-class device-"
                        "time waterfall with roofline verdicts "
                        "(telemetry/profile.py): 'profile' events in "
                        "the metrics JSONL, TensorBoard scalars, and "
                        "device_time_ms{op_class} rows in --prom-dump")
    p.add_argument("--prom-dump", default="",
                   help="write the train Prometheus exposition (goodput "
                        "fractions, MFU, step-time percentiles, restart "
                        "count, heartbeat age) to this file atomically at "
                        "every goodput report — the textfile-collector "
                        "transport, same as tpuic.serve's flag")
    p.add_argument("--slo", default="",
                   help="step-time SLOs, comma list of "
                        "'train_step:pQ<=Nms[@target]' specs "
                        "(telemetry/slo.py): rolling attainment and "
                        "error-budget burn rate land in the metrics "
                        "JSONL ('slo' events), TensorBoard, and the "
                        "--prom-dump exposition")
    return p


def config_from_args(args: argparse.Namespace) -> Config:
    auto_weights = (not args.no_class_weights
                    and list(args.class_weights) == ["auto"])
    if args.no_class_weights or auto_weights:
        weights = ()
    else:
        try:
            weights = tuple(float(w) for w in args.class_weights)
        except ValueError:
            raise SystemExit(
                "train.py: error: --class-weights expects numbers or the "
                f"single word 'auto' (got {args.class_weights!r})")
    if args.slo:
        # Validate the SLO grammar up front: a typo'd objective must fail
        # the command line, not crash Trainer construction minutes later.
        from tpuic.telemetry.slo import parse_objectives
        try:
            parse_objectives(args.slo, allowed=("train_step",))
        except ValueError as e:
            raise SystemExit(f"train.py: error: --slo: {e}")
    return Config(
        data=DataConfig(data_dir=args.datadir, resize_size=args.resize,
                        batch_size=args.batchsize, num_workers=args.workers,
                        val_batch_size=args.val_batchsize,
                        prefetch=args.prefetch,
                        device_cache_mb=args.device_cache_mb,
                        pack=not args.no_pack, cache_dir=args.cache_dir,
                        augment=not args.no_augment,
                        native=not args.no_native,
                        quarantine=not args.no_quarantine),
        model=ModelConfig(name=args.model, num_classes=args.num_classes,
                          dtype=args.dtype, attention=args.attention,
                          remat=args.remat, remat_policy=args.remat_policy,
                          drop_path=args.drop_path,
                          bn_f32_stats=not args.bn_bf16_stats,
                          compute_dtype=args.compute_dtype),
        optim=OptimConfig(optimizer=args.optimizer, learning_rate=args.lr,
                          milestones=tuple(args.milestones), gamma=args.gamma,
                          class_weights=weights,
                          auto_class_weights=auto_weights,
                          weight_decay=args.weight_decay,
                          grad_clip_norm=args.clip_grad_norm,
                          mixup_alpha=args.mixup,
                          cutmix_alpha=args.cutmix,
                          random_erase=args.random_erase,
                          warmup_epochs=args.warmup_epochs,
                          base_batch_size=args.base_batch,
                          grad_accum_steps=args.grad_accum_steps,
                          label_smoothing=args.label_smoothing,
                          ema_decay=args.ema_decay,
                          freeze_backbone=args.freeze_backbone,
                          fused_loss=args.fused_loss,
                          fused_optimizer=args.fused_optimizer,
                          loss_scale=args.loss_scale,
                          skip_nonfinite=not args.no_skip_guard),
        run=RunConfig(epochs=args.epochs, ckpt_dir=args.ckpt_dir,
                      save_period=args.save_period, resume=not args.no_resume,
                      init_from=args.init_from,
                      log_every_steps=args.log_every_steps,
                      collect_misclassified=args.collect_misclassified,
                      per_class_metrics=args.per_class_metrics,
                      profile_dir=args.profile_dir, seed=args.seed,
                      skip_threshold=args.skip_threshold,
                      rollback=not args.no_rollback,
                      rollback_rewarm_steps=args.rewarm_steps,
                      max_steps=args.steps,
                      metrics_jsonl=args.metrics_jsonl,
                      trace_dir=args.trace_dir,
                      trace_threshold=args.trace_threshold,
                      trace_steps=args.trace_steps,
                      trace_analyze=args.trace_analyze,
                      slo=args.slo,
                      async_checkpoint=not args.no_async_checkpoint),
        mesh=MeshConfig(model=args.model_axis, seq=args.seq_axis,
                        fsdp=args.fsdp, zero1=args.zero1),
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # Supervision protocol (runtime/supervisor.py, docs/robustness.md):
    # register the SIGQUIT handlers FIRST — a hang anywhere after this
    # line, including inside backend init or the first compile,
    # must still be explainable when the supervisor's watchdog
    # escalates. The flight recorder (telemetry/flight.py) registers
    # its Python-level dump BEFORE the faulthandler stack dump, which
    # chains into it: one SIGQUIT yields stacks + the event timeline
    # leading into the wedge. Costs nothing unsupervised (no
    # TPUIC_FLIGHT_DUMP -> no recorder, chain=False as before); the
    # import pulls no backend init.
    from tpuic.runtime.supervisor import (EXIT_POISON, EXIT_PREEMPTED,
                                          NonRetryableError,
                                          install_stack_dump_handler)
    from tpuic.telemetry.flight import install_flight_recorder
    flight = install_flight_recorder()
    install_stack_dump_handler(chain=flight is not None)
    from tpuic.telemetry.spans import span
    with span("import", module="train"):    # jax, flax, the whole program
        from tpuic.compiled.cache import enable_compile_cache
        from tpuic.metrics.logging import host0_print
        from tpuic.runtime.distributed import initialize
        from tpuic.train.loop import Trainer

    # The trainer runs on the platform JAX gives it and says which.
    cache_dir = enable_compile_cache()
    info = initialize()
    host0_print(f"[tpuic] {info.process_count} process(es), "
                f"{info.global_device_count} {info.platform} device(s), "
                f"device_kind={info.device_kind!r}; jax.distributed: "
                f"{info.distributed or 'not initialized (single host)'}; "
                f"compile cache: {cache_dir}")
    cfg = config_from_args(args)
    try:
        # Construction is in the poison scope too: a --resume restore
        # that finds every checkpoint rung corrupt raises here, before
        # fit() — it must exit 44, not crash-loop the supervisor through
        # the same corrupt rungs.
        trainer = Trainer(cfg, log_dir=args.log_dir or None)
    except NonRetryableError as e:
        host0_print(f"[tpuic] NON-RETRYABLE: {e}")
        return EXIT_POISON
    from tpuic import native
    from tpuic.kernels import default_interpret, default_opt_impl
    host0_print(f"[tpuic] model={trainer.model.backbone.__class__.__name__} "
                f"classes={trainer.model.num_classes} "
                f"mesh={dict(trainer.mesh.shape)}; input path: "
                f"pack={cfg.data.pack}, native decode="
                f"{cfg.data.native and native.decode_available()}, "
                f"native prep={cfg.data.native and native.available()}; "
                f"pallas kernels: interpret={default_interpret()}, "
                f"fused optimizer impl={default_opt_impl()!r}")
    if args.prom_dump:
        # Textfile-collector exposition, refreshed at each goodput report
        # (per epoch + final): the trainer already publishes the full
        # report as a 'goodput' event, so the dump is one more host-side
        # bus subscriber — no new syncs, no polling thread.
        from tpuic.metrics.logging import is_host0
        from tpuic.telemetry.events import subscribe
        from tpuic.telemetry.prom import train_exposition, write_exposition
        if is_host0():
            def _prom_dump(ev) -> None:
                hb = trainer.telemetry.heartbeat
                slo = trainer.telemetry.slo
                prof = trainer.telemetry.profile
                write_exposition(args.prom_dump, train_exposition(
                    dict(ev.data),
                    trainer.telemetry.steptime.summary(),
                    heartbeat_age_s=hb.age_s() if hb is not None else None,
                    slo=slo.report() if slo is not None else None,
                    memory=trainer.telemetry.memory.snapshot(),
                    profile=prof.last if prof is not None else None,
                    counters=trainer.last_counters))
            subscribe(_prom_dump, kinds=("goodput",))
    try:
        best = trainer.fit()
    except NonRetryableError as e:
        # The poison half of the exit-code contract: a supervisor restart
        # cannot fix this (rollback budget exhausted, every checkpoint
        # rung corrupt) — exit 44 so it reports instead of crash-looping.
        host0_print(f"[tpuic] NON-RETRYABLE: {e}")
        return EXIT_POISON
    if cfg.run.handle_preemption and trainer.preemption.triggered:
        # Clean preemption flush: the step-exact 'latest' checkpoint is
        # committed — exit 43 so a supervisor restarts with resume
        # (immediately, no backoff) instead of booking a crash.
        host0_print(f"[tpuic] preempted (flushed); best val accuracy "
                    f"{best:.4f}")
        return EXIT_PREEMPTED
    if getattr(trainer.telemetry, "slo", None) is not None:
        host0_print(f"[slo] {trainer.telemetry.slo.summary_line()}")
    host0_print(f"[tpuic] done; best val accuracy {best:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
