"""Configuration dataclasses.

The reference exposes 3 argparse flags (``--local_rank``, ``--datadir``,
``--batchsize``; train.py:27-31) and hard-codes everything else as inline
constants. Every one of those constants is surfaced here as a named field with
the reference's exact default (source lines cited per field), so behavior
parity is a config choice rather than an archaeology project.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Input-pipeline settings (reference dp/loader.py + train.py:110-118)."""

    data_dir: str = ""
    # Image side length; reference hard-codes 299 (train.py:110).
    resize_size: int = 299
    # Per-device train batch size; reference default 4 per process (train.py:30).
    batch_size: int = 4
    # Reference uses val batch_size=1 (train.py:118). We default to the train
    # batch size because SPMD eval is exact regardless of batching (the
    # reference needed bs=1 only for its per-sample pickle all_gather), but the
    # knob exists for strict parity runs.
    val_batch_size: int = 0  # 0 => same as batch_size
    # Host-side prefetch depth and worker threads (reference: num_workers=6,
    # pin_memory=True, train.py:114).
    num_workers: int = 6
    prefetch: int = 2
    # ImageNet normalization stats (reference dp/loader.py:86-91).
    mean: Sequence[float] = (0.485, 0.456, 0.406)
    std: Sequence[float] = (0.229, 0.224, 0.225)
    # Use the fused C++ prep core (tpuic/native) when its build is available;
    # False forces the pure-NumPy transform path (identical numerics).
    native: bool = True
    # Packed uint8 cache (tpuic/data/pack.py): decode+resize once into a
    # memory-mapped .bin, then serve epochs at memory bandwidth with
    # augmentation/normalization on the TPU (tpuic/data/device_prep.py).
    # The round-3 measured reality: this host has ONE core, so per-epoch
    # decode (reference dp/loader.py:44 every epoch) caps at ~220 img/s
    # while the chip consumes ~2,200 — packing is how the chip stays fed.
    pack: bool = True
    cache_dir: str = ""  # '' => {data_dir}/.tpuic_pack
    # Device-resident dataset cache: when the packed uint8 dataset fits
    # this HBM budget, the Loader uploads it ONCE (replicated under a mesh)
    # and a training batch ships only [B] indices + [B,5] augment params —
    # the gather/augment/normalize runs on device. Decouples the loop from
    # host-link bandwidth entirely. 0 disables.
    device_cache_mb: int = 4096
    # Global shuffle seed. The reference shuffles the file list per-rank,
    # unseeded (dp/loader.py:23) — a correctness bug (ranks see inconsistent
    # shards). We seed identically on every host and fold in the epoch.
    shuffle_seed: int = 0
    # Train-fold augmentation master switch. The reference hard-wires its
    # rot90/flip/jitter chain on every train sample (dp/loader.py:63-83);
    # that chain assumes orientation-free imagery. For orientation-sensitive
    # datasets (digits: rot90/flip alias 6<->9, 2<->5) False trains on clean
    # decodes while val/normalization behavior is unchanged.
    augment: bool = True
    # Augmentation probabilities (reference dp/loader.py:63-83).
    p_vflip: float = 0.5
    p_hflip: float = 0.5
    p_saturation: float = 0.05
    p_brightness: float = 0.05
    p_contrast: float = 0.05
    jitter_lo: float = 0.9
    jitter_hi: float = 1.1
    # Sample quarantine (docs/robustness.md): a sample whose decode fails
    # (truncated/corrupt file) is retried ``quarantine_retries`` times with
    # ``quarantine_backoff_s`` between attempts (the file-mid-copy case),
    # then replaced by a deterministic same-class substitute and counted —
    # one corrupt file degrades the epoch by one sample instead of killing
    # the producer thread (reference dp/loader.py has no handling at all).
    # False restores fail-fast: the decode error propagates and aborts.
    quarantine: bool = True
    quarantine_retries: int = 1
    quarantine_backoff_s: float = 0.05

    def resolved_val_batch_size(self) -> int:
        return self.val_batch_size or self.batch_size


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Model settings (reference nn/classifier.py + train.py:122-123)."""

    # Backbone name; reference default 'inceptionv3' (train.py:122).
    name: str = "inceptionv3"
    num_classes: int = 7
    # MLP head widths (reference nn/classifier.py:26-34: in->128->64->32->n).
    head_widths: Sequence[int] = (128, 64, 32)
    # Compute dtype. bfloat16 feeds the MXU at full rate; params stay f32.
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # BatchNorm momentum/eps matching torch defaults the reference inherits.
    bn_momentum: float = 0.9  # flax convention: ema = m*ema + (1-m)*batch
    bn_eps: float = 1e-5
    # BN batch-statistics accumulation dtype. True (default) reduces in
    # float32 — torch/SyncBN semantics. False reduces in the compute dtype
    # (bf16): the stat fusions re-read large activation tensors and are the
    # top HBM consumers in the ResNet-50 profile (builder, <= 2026-08-01), so
    # halving their read width is a bandwidth experiment (VERDICT r3 item
    # 7); numerics tolerance is pinned in tests/test_models.py. ResNet
    # family only; inception/effnet keep f32 stats.
    bn_f32_stats: bool = True
    # Rematerialize the forward in the backward pass (jax.checkpoint with the
    # dots-without-batch-dims policy): trades recompute FLOPs for activation
    # HBM traffic/footprint — a win when the model is bandwidth-bound or
    # memory-limited. The reference has no equivalent (torch would need
    # torch.utils.checkpoint rewiring).
    remat: bool = False
    # What remat recomputes (effective only when remat=True). 'dots' is the
    # step's and applies to every model; the others are flags of the
    # backbone, and which a family has is what it declares when registered
    # (models.Family.remat_policies) — elsewhere they warn and no-op:
    #   'dots'      — whole-forward jax.checkpoint saving only matmul/conv
    #                 outputs without batch dims; recomputes all
    #                 activation-sized tensors (the original behavior;
    #                 measured -15..20% on ResNet-50, PERF_ANALYSIS.md §1).
    #   'attention' — ViT ``remat_core``: just the logits->softmax->probs@v
    #                 core runs under jax.checkpoint, so the [B,H,N,N]
    #                 tensors that erase allocator headroom past b64 (§10b)
    #                 are never residuals; recompute is one einsum + softmax
    #                 per layer. No-op for models/impls with no dense
    #                 attention core (ResNet; flash never materializes it).
    #   'blocks'    — ViT and looped-stack (ouro-*) ``remat_blocks``: each
    #                 block under nn.remat with the save-nothing policy, so
    #                 the only N-sized residuals are the block inputs and
    #                 the backward recomputes one block at a time. The
    #                 ViT's long-context memory mode: at N=4097/b16 'dots'
    #                 needs 19.5 GB (flash) / 41.1 GB (dense) vs 15.75 HBM
    #                 (PERF_ANALYSIS.md §10f). For the looped stack it is
    #                 the memory mode: activations grow with layers x
    #                 passes, weights do not. Composes with any attention
    #                 impl.
    #   'gelu'      — ViT ``remat_mlp``: each block's Dense(mlp_up)+GELU
    #                 runs under nn.remat (models/vit.py MlpUpGelu), so
    #                 the [B,N,4D] pre-activation is never a residual —
    #                 the mlp_up fusion writes ONE output instead of two
    #                 and the backward recomputes W1·x per block. The
    #                 lightest policy, aimed at the dual-output mlp_up
    #                 writes the ViT-B b64 profile fingered (§10f). In
    #                 MoE ViTs the dense-MLP blocks still benefit (the
    #                 routed SwitchMoEMlp blocks are untouched).
    remat_policy: str = "dots"
    # Inception aux-logits loss weight (reference train.py:52).
    aux_loss_weight: float = 0.4
    # Looped models (models/ouro.py): weight beta of the entropy term in
    # ``train/loss.py::exit_expected_loss``, which keeps the learned exit
    # distribution from collapsing onto one pass.
    exit_entropy_weight: float = 0.05
    # MoE load-balancing loss weight (Switch Transformer's alpha; only
    # active for *-moe models, which sow 'moe_router' stats that the train
    # step turns into a padding-masked switch_aux_loss).
    moe_aux_weight: float = 0.01
    # Inference-only Pallas fused conv+BN+ReLU for the ResNet family
    # (tpuic/kernels/conv_bn_relu.py): every conv -> BN -> ReLU block of
    # a train=False call runs as one VMEM-resident kernel (conv as tap
    # matmuls, BN folded to a per-channel affine epilogue) instead of
    # three HBM-roundtripping HLOs. Parameter structure is unchanged, so
    # the flag flips on any existing checkpoint; training and non-ResNet
    # backbones ignore it. Numerics parity vs the unfused graph is
    # pinned in tests/test_kernels.py (atol 1e-4 in float32).
    fused_conv_bn: bool = False
    # Attention implementation for attention-bearing backbones (ViT):
    # 'dense' (einsum softmax), 'flash' (Pallas blockwise online-softmax,
    # tpuic/kernels/flash_attention.py), 'ring' (sequence-parallel ring
    # attention over the mesh 'seq' axis, tpuic/parallel/ring_attention.py),
    # 'ring-flash' (the ring with the flash kernel as its per-step block
    # primitive — long-context), 'ulysses' (sequence-parallel all-to-all
    # head redistribution, tpuic/parallel/ulysses.py), or 'ulysses-flash'
    # (ulysses with its head-sharded local attention run through the flash
    # kernel). CNNs ignore this.
    attention: str = "dense"
    # Stochastic depth for ViT backbones (rate of the LAST block; rates
    # ramp linearly from 0 — the DeiT schedule). CNNs ignore this.
    drop_path: float = 0.0
    # Training compute-dtype POLICY ('' | 'bf16' | 'f32'), wired through
    # ``train.py --compute-dtype``. '' (default) leaves the per-model
    # ``dtype`` field in charge — bitwise the pre-policy behavior. 'bf16'
    # is the mixed-precision training tier: the forward/backward run in
    # bfloat16 (``dtype`` is forced, batch images are cast at the step
    # entry) while the differentiated MASTER params stay float32
    # (``param_dtype``), the optimizer moments stay float32 (optax init
    # mirrors the f32 params), the loss is computed on f32 logits, and
    # checkpoints stay float32 on disk — the lifecycle / hot-swap /
    # elastic machinery never sees a dtype change. 'f32' forces full
    # float32 compute: the convergence-parity reference arm
    # (scripts/bf16_parity.py, the tier-1 "bf16 parity" CI gate).
    compute_dtype: str = ""

    def __post_init__(self):
        resolve_compute_dtype(self)  # validate eagerly, not at trace time


# Accepted spellings of the ModelConfig.compute_dtype policy -> canonical
# tag. '' = legacy (per-model dtype field rules).
_COMPUTE_DTYPES = {"": "", "bf16": "bf16", "bfloat16": "bf16",
                   "f32": "f32", "float32": "f32"}


def resolve_compute_dtype(model: "ModelConfig") -> str:
    """Canonical compute-dtype tag for a ModelConfig: '', 'bf16' or 'f32'.

    The single normalization point: the Trainer (model dtype override +
    telemetry roofline choice) and the train step (batch cast, f32-loss
    guarantee) must agree on what the policy means."""
    key = str(getattr(model, "compute_dtype", "") or "").lower()
    if key not in _COMPUTE_DTYPES:
        raise ValueError(
            f"unknown compute_dtype {model.compute_dtype!r}; expected one "
            f"of {sorted(k for k in _COMPUTE_DTYPES if k)} (or '' for the "
            "per-model dtype default)")
    return _COMPUTE_DTYPES[key]


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    """Optimizer + schedule (reference train.py:127, 156-158)."""

    optimizer: str = "adam"  # 'adam' | 'lars' | 'sgd'
    # Reference lr=0.5e-5 (train.py:127).
    learning_rate: float = 0.5e-5
    # MultiStepLR milestones=[50, 80], gamma=0.5 (train.py:156).
    milestones: Sequence[int] = (50, 80)
    gamma: float = 0.5
    # Class weights for CrossEntropy; reference hard-codes a 7-class imbalance
    # vector (train.py:157-158). Empty => unweighted.
    class_weights: Sequence[float] = (3.0, 3.0, 10.0, 1.0, 4.0, 4.0, 5.0)
    # Derive inverse-frequency weights from the train fold's class counts
    # (w_c = N / (K * n_c), mean ~1) at Trainer construction — what the
    # reference's hard-coded vector approximated by hand for its original
    # 7-class dataset. Overrides class_weights.
    auto_class_weights: bool = False
    weight_decay: float = 0.0
    # Mixup (Zhang et al., 2018): Beta(alpha, alpha) convex image/label
    # mixing, applied on-device inside the jitted train step (one lambda
    # per step). 0 disables; 0.2 is the common ImageNet setting.
    mixup_alpha: float = 0.0
    # CutMix (Yun et al., 2019): Beta(alpha, alpha)-sized box from the
    # permuted partner pasted per step, labels mixed by EXACT kept area.
    # 0 disables; 1.0 is the paper setting. When both mixup and cutmix
    # are set, one is chosen per step (50/50, torchvision recipe).
    cutmix_alpha: float = 0.0
    # Random erasing (Zhong et al., 2020): per-sample probability of
    # zeroing a random box (2-33% area) on-device in the train step.
    # 0 disables; 0.25 is the common timm setting.
    random_erase: float = 0.0
    # LARS settings for the large-batch config (BASELINE.md config 5).
    lars_momentum: float = 0.9
    lars_trust_coefficient: float = 0.001
    # LAMB (arXiv:1904.00962) moments — the Adam-flavored layer-wise
    # trust-ratio optimizer for large-batch attention models
    # (optimizer='lamb'; weight_decay rides the shared knob).
    lamb_b1: float = 0.9
    lamb_b2: float = 0.999
    lamb_eps: float = 1e-6
    # Goyal linear-scaling rule (arXiv:1706.02677; every 15-minute-
    # ImageNet recipe's ingredient): when > 0, the peak LR becomes
    # learning_rate * global_batch / base_batch_size, reached by a
    # LINEAR warmup from the unscaled learning_rate over warmup_epochs
    # (train/schedule.py batch_scaled_warmup_schedule). The global batch
    # is per-device batch x data-parallel extent, so the SAME config
    # stays correctly tuned as the fleet grows — or elastically shrinks
    # (the re-formed mesh rebuilds the schedule at its new extent).
    # 0 (default) disables scaling entirely.
    base_batch_size: int = 0
    warmup_epochs: int = 0
    grad_clip_norm: float = 0.0
    # Accumulate gradients over K steps before applying one optimizer
    # update (effective batch = K * global batch). 1 = off.
    grad_accum_steps: int = 1
    label_smoothing: float = 0.0
    # Exponential moving average of params (0 = off; typical 0.9999).
    # ema = d*ema + (1-d)*params after each real optimizer update;
    # validation, checkpoint 'best' selection, and predict then use the
    # EMA weights — the standard modern image-classification recipe
    # (EfficientNet/ViT). Must be in [0, 1): 1.0 would freeze the EMA at
    # its seed forever (validated in __post_init__).
    ema_decay: float = 0.0
    # Head-only fine-tuning: zero updates for the backbone scope, so only
    # the MLP head trains (pairs with RunConfig.init_from). Gradient-level
    # freeze — BN running stats still update in train mode.
    freeze_backbone: bool = False
    # Use the fused Pallas cross-entropy kernel
    # (tpuic/kernels/cross_entropy.py) in the train step.
    fused_loss: bool = False
    # Fused one-pass optimizer-update kernel for 'lars' / 'lamb'
    # (tpuic/kernels/optimizer_update.py): params, grads and moments make
    # ONE VMEM round trip per leaf instead of the optax chain's stacked
    # elementwise HLOs (decay -> trust -> lr -> momentum each
    # materializing an update-sized tree). Trajectory parity vs the
    # optax chain and the numpy trust-ratio references is golden-pinned
    # in tests/test_fused_optimizer.py; off-TPU the same math runs as a
    # single fused jnp pass (graceful fallback — no Pallas required).
    # NOTE: the fused opt_state layout differs from optax's chain state,
    # so flipping this over an existing checkpoint restores through the
    # lenient path (optimizer moments reset; params are untouched).
    fused_optimizer: bool = False
    # Static loss scaling for bf16 training (ModelConfig.compute_dtype):
    # the step multiplies the loss by this factor before the backward
    # pass and unscales the gradients after, lifting tiny gradients over
    # bf16 underflow. 1.0 = off, the right default for the TPU-style
    # bf16 recipe (f32 master weights, f32 grads out of the cast-site
    # VJPs) — the knob exists for stress runs. An overflowed scaled step
    # surfaces as non-finite grads and rides the skip_nonfinite guard.
    loss_scale: float = 1.0
    # Non-finite step guard (docs/robustness.md): the train step checks
    # loss/grad-norm finiteness in-graph and applies the optimizer update
    # under lax.cond — a NaN/Inf batch leaves params, opt_state, EMA, BN
    # stats, and the step counter UNCHANGED and sets metrics['skipped'],
    # with zero recompiles (the guard is part of the one compiled program).
    # Large-batch regimes make transient non-finite steps an expected
    # event, not an anomaly (arXiv:1711.04325). False removes the cond
    # (bitwise the unguarded step; NaN then poisons state permanently).
    skip_nonfinite: bool = True

    def __post_init__(self):
        if not 0.0 <= self.ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in [0, 1); got {self.ema_decay} "
                "(1.0 would freeze the EMA at its random seed forever)")
        if not 0.0 <= self.random_erase <= 1.0:
            raise ValueError(
                f"random_erase is a PROBABILITY in [0, 1]; got "
                f"{self.random_erase} (mixup/cutmix use alpha-style "
                "knobs, this one does not)")
        if not self.loss_scale > 0.0:
            raise ValueError(
                f"loss_scale must be > 0; got {self.loss_scale} "
                "(1.0 disables scaling)")


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Training-loop + checkpoint settings (reference train.py:131-188)."""

    epochs: int = 100  # reference range(100), train.py:161
    ckpt_dir: str = "dtmodel/cp"  # reference train.py:136
    save_period: int = 5  # 'latest' every 5 epochs, train.py:183
    resume: bool = True
    # Initialize params/batch_stats from a torch checkpoint (reference-layout
    # ``{'state_dict': ...}`` file or bare state_dict; backbone family
    # auto-detected) via the converter + lenient restore. The reference
    # starts every backbone from pretrained torch weights
    # (nn/classifier.py:9-21); this is the switch-over path for those users.
    init_from: str = ""
    # Console/JSONL metric cadence. Every log forces a device->host scalar
    # readback that blocks dispatch, so logging every step serializes the
    # pipeline (round-2 finding: bench-grade throughput is unattainable at
    # 1). 50 keeps the readback off the steady-state critical path; the
    # progress-bar UX (reference train.py:67-68 updates every step) is
    # preserved via the async metrics buffer in train/loop.py.
    log_every_steps: int = 50
    # Collect the image ids of misclassified val samples each epoch
    # (Trainer.last_misclassified + a logged count). The per-sample
    # correctness vector is returned replicated from the sharded eval step —
    # GSPMD's all-gather over ICI — the fixed-shape redesign of the
    # reference's pickle all_gather of ragged per-sample data
    # (ddp_utils.py:16-56).
    collect_misclassified: bool = False
    # Per-class validation metrics: the eval step adds a fixed-shape [C,C]
    # confusion contraction (true x predicted counts, GSPMD-reduced like
    # every other eval sum); val_epoch logs exact global per-class accuracy
    # and saves the summed confusion matrix beside the metrics JSONL.
    # The aggregate view of the reference's misclassified-image analysis
    # (train.py:88-92).
    per_class_metrics: bool = False
    # Profiler trace dir ('' disables). The reference has no profiling at all
    # (SURVEY.md §5); jax.profiler makes it nearly free so it is first-class.
    profile_dir: str = ""
    profile_steps: int = 0
    # Install a SIGTERM latch (runtime/preemption.py): on pod preemption /
    # scheduler eviction the loop finishes its step, flushes a 'latest'
    # checkpoint, and returns instead of dying mid-epoch. The reference
    # loses everything since the last periodic save (SURVEY.md §5).
    handle_preemption: bool = True
    # Rollback on a non-finite streak (docs/robustness.md): when the
    # in-graph guard (OptimConfig.skip_nonfinite) has skipped this many
    # CONSECUTIVE steps, the Trainer stops grinding forward, restores the
    # last good checkpoint through the integrity ladder, and continues
    # from there. Detection rides the deferred metrics drain, so latency
    # is up to ~2 log intervals (log_every_steps). 0 disables detection.
    skip_threshold: int = 10
    rollback: bool = True
    # Give up after this many rollbacks in one fit() — persistent
    # non-finite data would otherwise loop restore->skip->restore forever.
    max_rollbacks: int = 3
    # After a rollback, ramp the LR linearly from ~0 back to the schedule
    # over this many steps (loss-spike hygiene per the large-batch
    # literature). Costs ONE retrace of the train step per rollback
    # (the optimizer schedule changes); 0 keeps the plain schedule and
    # stays retrace-free.
    rollback_rewarm_steps: int = 0
    seed: int = 0
    # -- telemetry (tpuic/telemetry, docs/observability.md) ------------
    # Stop after this many optimizer steps regardless of epochs (0 = no
    # cap). Smoke runs and the CI telemetry gate use it; a mid-epoch
    # stop skips the epoch's val pass.
    max_steps: int = 0
    # Telemetry event JSONL sink ('' disables): one line per bus event —
    # per-step time breakdown, skip/rollback/quarantine/checkpoint
    # events, compile durations, and the final goodput report.
    metrics_jsonl: str = ""
    # Triggered profiler traces (telemetry/tracing.py): when set, a step
    # slower than trace_threshold x the rolling median starts a
    # jax.profiler window of trace_steps steps under trace_dir, keeping
    # at most trace_keep traces. '' disables; the TPUIC_TRACE env var
    # overrides the dir AND forces one immediate window.
    trace_dir: str = ""
    trace_threshold: float = 3.0
    trace_steps: int = 3
    trace_keep: int = 4
    # Device-time attribution (telemetry/profile.py): auto-analyze every
    # captured trace window (and the run's full step history at fit()
    # end) into a per-op-class roofline waterfall published as 'profile'
    # events.  Off by default: the analysis AOT-compiles the train step
    # once for its HLO/cost-analysis view.
    trace_analyze: bool = False
    # Step-time SLOs (telemetry/slo.py): comma list of objective specs,
    # e.g. 'train_step:p99<=500ms@0.99'. Rolling attainment and
    # error-budget burn rate ride the goodput log line, the 'slo' bus
    # events, and the Prometheus exposition. '' disables.
    slo: str = ""
    # Async checkpoint commits (docs/robustness.md "Async checkpoint
    # commits"): a save stages its write and returns; the manifest walk
    # and the .new -> track rotation run on a background thread, so the
    # goodput 'checkpoint' bucket measures ~0 instead of the blocking
    # commit span. Deferred, never early — the track-level manifest that
    # gang.committed_steps / fleet_resume_step read still appears only
    # at rotation, so a rank can never advertise a commit the fleet
    # cannot restore. Multi-host runs fall back to synchronous commits
    # (the commit barrier is a collective and must stay on the main
    # thread). False restores blocking commits everywhere.
    async_checkpoint: bool = True


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh axes.

    The reference's only strategy is data parallelism (train.py:128). We build
    the mesh with a ``data`` axis (batch sharding — the DDP equivalent), a
    ``seq`` axis (sequence/context parallelism: ring attention shards the
    token dim of attention-bearing models over it), and a ``model`` axis
    (Megatron-style tensor parallelism over attention heads / MLP hidden).
    seq=1, model=1 means pure DP — reference parity.
    data=0 => inferred from device count.
    """

    data: int = 0  # 0 => all devices / (seq * model)
    seq: int = 1
    model: int = 1
    axis_names: Sequence[str] = ("data", "seq", "model")
    # FSDP/ZeRO-3: shard large params + Adam moments over the data axis
    # (tpuic/parallel/sharding.py). False => replicated state, DDP semantics.
    fsdp: bool = False
    # ZeRO-1 weight-update sharding (arXiv:2004.13336): params replicated
    # (pure-DP forward, no weight gathers) but optimizer moments sharded
    # over 'data' — 1/N Adam memory and update compute per device, one
    # update all-gather per step. Subsumed by fsdp=True.
    zero1: bool = False
    # Map models' logical 'model' axis onto the mesh model axis (Megatron TP).
    # Only meaningful when model > 1.
    tensor_parallel: bool = True


@dataclasses.dataclass(frozen=True)
class Config:
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    optim: OptimConfig = dataclasses.field(default_factory=OptimConfig)
    run: RunConfig = dataclasses.field(default_factory=RunConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


def cifar10_config(data_dir: str = "") -> Config:
    """BASELINE.md parity config 1: ResNet-18 / CIFAR-10, single process."""
    return Config(
        data=DataConfig(data_dir=data_dir, resize_size=32, batch_size=128),
        model=ModelConfig(name="resnet18", num_classes=10),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3, class_weights=()),
    )


def imagenet_resnet50_config(data_dir: str = "") -> Config:
    """BASELINE.md parity config 2: ResNet-50 / ImageNet, data parallel."""
    return Config(
        data=DataConfig(data_dir=data_dir, resize_size=224, batch_size=256),
        model=ModelConfig(name="resnet50", num_classes=1000),
        optim=OptimConfig(optimizer="lars", learning_rate=4.8, class_weights=(),
                          weight_decay=1e-4, warmup_epochs=5),
        run=RunConfig(epochs=90),
    )
