"""Compiled train / eval steps.

This is the TPU-native replacement for the reference's entire hot loop
(train.py:44-73) and validation pass (train.py:78-97). The whole per-batch
body — forward, loss (+0.4·aux for inception), backward, cross-replica
gradient averaging, BN stat sync, optimizer update, and metric reductions — is
ONE jitted XLA program over the device mesh:

- The batch is sharded over the ``data`` mesh axis; reductions over the batch
  dim (loss mean, BN statistics, accuracy counts) are *global* reductions, so
  GSPMD inserts the all-reduces that DDP (train.py:128), SyncBatchNorm
  (train.py:124), the logging all-reduce (train.py:61-63), and the pickle
  all_gather (ddp_utils.py:16-56) performed eagerly in the reference. XLA's
  latency-hiding scheduler overlaps the gradient reductions with the backward
  pass — the compiled analogue of DDP's bucket overlap.
- No separate no_grad logging collective: the loss metric IS the globally
  averaged loss, free.
- Validation returns exact global (weighted-correct, count) sums — the
  static-shape redesign of the reference's ragged per-sample gather; padded
  samples carry mask 0 and thus contribute to neither numerator nor
  denominator, which *fixes* the DistributedSampler padding-duplicate skew
  noted in SURVEY.md §7.
"""

from __future__ import annotations

import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuic.config import ModelConfig, OptimConfig, resolve_compute_dtype
from tpuic.metrics.meters import accuracy, topk_accuracy
from tpuic.models import MODEL_REMAT_POLICIES, family
from tpuic.models.classifier import ExitOutputs
from tpuic.train.loss import classification_loss, exit_expected_loss
from tpuic.train.state import TrainState


def _batch_shardings(mesh: Mesh):
    """Batch dict: every leaf sharded on dim 0 over the data axis."""
    return NamedSharding(mesh, P("data"))


def _moe_router_stats(intermediates) -> list:
    """All (probs, onehot) router tuples sown anywhere in the model
    (models/moe.py 'moe_router'); flax sow wraps each in an append-tuple,
    so pairs arrive as consecutive leaves under the same path."""
    by_path = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        if any(getattr(k, "key", None) == "moe_router" for k in path):
            key = tuple(str(k) for k in path[:-1])
            by_path.setdefault(key, []).append(leaf)
    for key, v in by_path.items():
        # Fail fast on sow-structure drift: silently dropping groups here
        # would silently drop the load-balancing loss from training.
        if len(v) != 2:
            raise ValueError(f"'moe_router' sow at {key} has {len(v)} "
                             "leaves; expected (probs, onehot)")
    return [tuple(v) for v in by_path.values()]


def _sown_counters(sown) -> dict:
    """The backbone's own counters of this step: what its modules sowed
    into the ``counters`` collection, by name, each the mean over the
    modules that sowed it (a routed layer's pairs and load,
    models/kanana.py: the mean over the expert layers)."""
    by_name = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        by_name.setdefault(name, []).append(leaf)
    return {name: sum(v) / len(v) for name, v in by_name.items()}


def _replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def resolve_remat_policy(model_cfg: ModelConfig):
    """Step-level jax.checkpoint policy for the config, or None.

    'dots': wrap the whole forward, keeping only matmul/conv outputs
    without batch dims (i.e. nothing activation-sized); the backward
    recomputes activations instead of round-tripping them through HBM.

    'attention', 'blocks' and 'gelu' return None on purpose: they live in
    the MODEL (its family's builder sets the backbone's flag from the
    config; ModelConfig.remat_policy says what each keeps as residuals).
    'attention' and 'gelu' are not expressible as a step-level names
    policy: softmax's backward wants its own internal output, and the
    mlp_up pre-activation's dtype-cast copies and erf-vjp internals are
    as large as it is, so a save-anything-except-names policy still saves
    them (verified with jax.ad_checkpoint.print_saved_residuals).
    """
    if not model_cfg.remat:
        return None
    policy = model_cfg.remat_policy
    if policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if policy not in MODEL_REMAT_POLICIES:
        raise ValueError(f"unknown remat_policy '{policy}'; available: "
                         f"{['dots', *MODEL_REMAT_POLICIES]}")
    implemented = family(model_cfg.name).remat_policies
    # 'attention' wraps the dense logits->softmax->probs@v core only (flash
    # never materializes it).
    if policy not in implemented or (policy == "attention"
                                     and model_cfg.attention != "dense"):
        # This combination applies NO remat at all — loud beats a silent
        # OOM at a batch size --remat (dots) would have fit.
        warnings.warn(
            f"remat_policy='{policy}' has no effect for model="
            f"'{model_cfg.name}' with attention='{model_cfg.attention}': "
            f"its backbone implements {sorted(implemented) or 'none'} of "
            f"{list(MODEL_REMAT_POLICIES)} ('attention' over a dense core "
            "only); NO remat is applied. Use remat_policy='dots' for "
            "whole-forward remat.",
            stacklevel=2)
    return None


# The step's own metrics; any other key of the metrics dict is a counter
# of the model's family, which the Trainer drains without knowing it.
STEP_METRICS = frozenset({"loss", "accuracy", "grad_norm", "skipped",
                          "skip_count", "lr"})


def make_train_step(optim_cfg: OptimConfig, model_cfg: ModelConfig,
                    mesh: Optional[Mesh] = None,
                    lr_schedule: Optional[optax.Schedule] = None,
                    donate: bool = True, seed: int = 0,
                    state_sharding=None) -> Callable:
    """Returns jitted ``train_step(state, batch) -> (state, metrics)``.

    batch: {'image': [B,H,W,3] f32, 'label': [B] i32, 'mask': [B] f32}.
    B is the *global* batch size; under a mesh the caller provides globally
    sharded arrays (tpuic.data.pipeline handles this).

    state_sharding: optional NamedSharding prefix tree for the TrainState
    (tpuic.parallel.sharding.state_shardings) — TP/FSDP param+opt sharding.
    None => fully replicated state (reference DDP semantics).
    """
    class_weights = (jnp.asarray(optim_cfg.class_weights, jnp.float32)
                     if optim_cfg.class_weights else None)
    aux_w = model_cfg.aux_loss_weight
    exit_beta = model_cfg.exit_entropy_weight
    smoothing = optim_cfg.label_smoothing
    remat_policy = resolve_remat_policy(model_cfg)
    # Mixed-precision policy (ModelConfig.compute_dtype): under 'bf16' the
    # batch is cast once at the step entry and the loss is computed on f32
    # logits; the Trainer has already forced the model's compute dtype.
    # The differentiated params stay f32 (param_dtype) — the in-module
    # casts' VJPs accumulate f32 grads — so master weights, moments, and
    # checkpoints never leave f32.
    compute_dtype = resolve_compute_dtype(model_cfg)
    cast_dtype = jnp.bfloat16 if compute_dtype == "bf16" else None
    loss_scale = float(optim_cfg.loss_scale or 1.0)
    # The cpu+cache+guard donation-disable rule lives in ONE place now:
    # tpuic.compiled.donation_allowed (docs/performance.md, "Compiled-
    # program registry").  The guard's skip path aliases donated inputs
    # straight to outputs (state passes through unchanged); executables
    # DESERIALIZED from the persistent compilation cache mishandle that
    # aliasing on this container's jax 0.4.37 CPU backend — silent
    # buffer corruption (NaN loss on finite data after a restore) and
    # nondeterministic SIGSEGV/SIGABRT in dispatch.  Cache+donate+guard
    # is the exact trigger; any two of the three are fine, so TPU runs
    # (and any run without a persistent cache) keep donation.
    from tpuic.compiled import donation_allowed
    if donate and not donation_allowed(
            guard_active=bool(optim_cfg.skip_nonfinite)):
        warnings.warn(
            "skip_nonfinite guard + persistent compilation cache: "
            "disabling train-state donation to avoid a known "
            "aliasing bug in cache-deserialized executables "
            "(independent of ModelConfig.compute_dtype / "
            "--compute-dtype: the bf16 tier's cast sites produce fresh "
            "arrays, never aliases of the donated state — set "
            "skip_nonfinite=False or drop the cache dir to keep "
            "donation)",
            stacklevel=2)
        donate = False

    def train_step(state: TrainState, batch):
        images, labels = batch["image"], batch["label"]
        mask = batch.get("mask")

        # Per-step dropout/drop-path randomness, deterministic in (seed, step).
        dropout_rng = jax.random.fold_in(jax.random.key(seed), state.step)

        # Mixup (Zhang et al., 2018) / CutMix (Yun et al., 2019), fully
        # on-device inside the jitted step: one lambda (and one box) per
        # step, pairs drawn by a global batch permutation (on a sharded
        # batch the gather is a GSPMD collective over ICI — one
        # batch-sized exchange per step). The loss becomes
        # lam*CE(y) + (1-lam)*CE(y_perm); accuracy is reported against
        # the ORIGINAL labels (standard practice). The Trainer's train
        # loader guarantees full batches (drop_last + the zero-steps
        # guard); for any other caller, rows whose pair involves a padded
        # sample fall back to SELF as the partner — self-mixing is the
        # exact identity, so partial batches degrade to plain CE per row
        # instead of training on padding garbage. With BOTH enabled, one
        # is chosen per step (50/50, the torchvision recipe) via
        # lax.cond, so only the chosen branch executes.
        labels_mix = None
        lam = None
        if optim_cfg.mixup_alpha > 0 or optim_cfg.cutmix_alpha > 0:
            mix_rng = jax.random.fold_in(dropout_rng, 0x6D69)
            perm = jax.random.permutation(jax.random.fold_in(mix_rng, 1),
                                          images.shape[0])
            partners = images[perm]
            labels_mix = labels[perm]
            if mask is not None:
                pair_ok = (mask * mask[perm]) > 0
                partners = jnp.where(pair_ok[:, None, None, None],
                                     partners, images)
                labels_mix = jnp.where(pair_ok, labels_mix, labels)

            def _mixup(imgs, partners):
                lam = jax.random.beta(mix_rng, optim_cfg.mixup_alpha,
                                      optim_cfg.mixup_alpha)
                out = (lam * imgs.astype(jnp.float32)
                       + (1.0 - lam) * partners.astype(jnp.float32))
                return out.astype(imgs.dtype), lam

            def _cutmix(imgs, partners):
                # Static-shape box: bounds are traced scalars compared
                # against iotas; the adjusted lambda is the EXACT kept
                # area (clipping at the borders changes it).
                h, w = imgs.shape[1], imgs.shape[2]
                lam0 = jax.random.beta(mix_rng, optim_cfg.cutmix_alpha,
                                       optim_cfg.cutmix_alpha)
                cut = jnp.sqrt(1.0 - lam0)
                cy, cx = jax.random.uniform(
                    jax.random.fold_in(mix_rng, 2), (2,))
                bh, bw = cut * h, cut * w
                y0 = jnp.clip(cy * h - bh / 2, 0, h)
                y1 = jnp.clip(cy * h + bh / 2, 0, h)
                x0 = jnp.clip(cx * w - bw / 2, 0, w)
                x1 = jnp.clip(cx * w + bw / 2, 0, w)
                ys = jnp.arange(h, dtype=jnp.float32)
                xs = jnp.arange(w, dtype=jnp.float32)
                box = ((ys[:, None] >= y0) & (ys[:, None] < y1)
                       & (xs[None, :] >= x0) & (xs[None, :] < x1))
                out = jnp.where(box[None, :, :, None], partners, imgs)
                lam = 1.0 - jnp.mean(box.astype(jnp.float32))
                return out, lam

            # Scope tag for the device-time waterfall (telemetry/
            # profile.py): mix ops roll up under 'augment', apart from
            # the model's own layers.
            with jax.named_scope("augment"):
                if optim_cfg.mixup_alpha > 0 and optim_cfg.cutmix_alpha > 0:
                    use_mix = jax.random.bernoulli(
                        jax.random.fold_in(mix_rng, 3))
                    # tpuic-ok: TPU202 cond operands are fresh mix
                    # tensors, never the donated pass-through state; the
                    # skip guard stays a jnp.where select (the PR-2
                    # bisect's actual fix)
                    images, lam = jax.lax.cond(  # tpuic-ok: TPU202
                        use_mix, _mixup, _cutmix, images, partners)
                elif optim_cfg.mixup_alpha > 0:
                    images, lam = _mixup(images, partners)
                else:
                    images, lam = _cutmix(images, partners)

        # Random erasing (Zhong et al., 2020), per SAMPLE: with prob p a
        # random box (area 2-33%, aspect 0.3-3.3) is zeroed — zero IS the
        # per-channel mean after the pipeline's normalization. Labels are
        # untouched, so it composes freely with mixup/cutmix above.
        if optim_cfg.random_erase > 0:
            with jax.named_scope("augment"):
                er_rng = jax.random.fold_in(dropout_rng, 0x6572)
                b, h, w = (images.shape[0], images.shape[1],
                           images.shape[2])
                ks = jax.random.split(er_rng, 5)
                area = jax.random.uniform(ks[0], (b,), minval=0.02,
                                          maxval=0.33)
                log_ar = jax.random.uniform(ks[1], (b,),
                                            minval=jnp.log(0.3),
                                            maxval=jnp.log(3.3))
                ar = jnp.exp(log_ar)
                bh = jnp.clip(jnp.sqrt(area * h * w * ar), 1, h)   # [B]
                bw = jnp.clip(jnp.sqrt(area * h * w / ar), 1, w)
                cy = jax.random.uniform(ks[2], (b,)) * h
                cx = jax.random.uniform(ks[3], (b,)) * w
                y0, y1 = (jnp.clip(cy - bh / 2, 0, h),
                          jnp.clip(cy + bh / 2, 0, h))
                x0, x1 = (jnp.clip(cx - bw / 2, 0, w),
                          jnp.clip(cx + bw / 2, 0, w))
                apply = jax.random.bernoulli(ks[4],
                                             optim_cfg.random_erase, (b,))
                ys = jnp.arange(h, dtype=jnp.float32)
                xs = jnp.arange(w, dtype=jnp.float32)
                box = ((ys[None, :, None] >= y0[:, None, None])
                       & (ys[None, :, None] < y1[:, None, None])
                       & (xs[None, None, :] >= x0[:, None, None])
                       & (xs[None, None, :] < x1[:, None, None])
                       & apply[:, None, None])                 # [B,H,W]
                images = jnp.where(box[..., None],
                                   jnp.zeros_like(images), images)

        if cast_dtype is not None:
            # bf16 compute tier: activations enter the network in bf16.
            # One cast of the batch — downstream params are cast inside
            # the flax modules (dtype=bfloat16) and its VJP accumulates
            # the gradient back in f32. After the augment block on
            # purpose: mixup/cutmix blend in f32 and random-erase masks
            # in the input dtype, identical to the f32 arm.
            with jax.named_scope("cast_bf16"):
                images = images.astype(cast_dtype)

        def forward(params, batch_stats, images, rng):
            variables = {"params": params, "batch_stats": batch_stats}
            # 'intermediates' carries sown MoE load-balancing losses
            # (models/moe.py), 'counters' what a backbone counts in a step
            # (models/kanana.py); empty for dense models.
            return state.apply_fn(variables, images, train=True,
                                  mutable=["batch_stats", "intermediates",
                                           "counters"],
                                  rngs={"dropout": rng})

        if remat_policy is not None:
            forward = jax.checkpoint(forward, policy=remat_policy)

        def loss_fn(params):
            if optim_cfg.freeze_backbone and "backbone" in params:
                # stop_gradient lets XLA prune the whole backbone backward
                # pass (the optimizer-side set_to_zero alone would still
                # compute it, since the grad_norm metric keeps raw grads
                # live); grad_norm then reflects the head-only update.
                params = {**params,
                          "backbone": jax.lax.stop_gradient(
                              params["backbone"])}
            out, mutated = forward(params, state.batch_stats, images,
                                   dropout_rng)
            if cast_dtype is not None:
                # f32-loss guarantee of the bf16 tier: log-softmax over
                # bf16 logits costs ~3 decimal digits right where the
                # parity gate measures.
                out = jax.tree.map(lambda t: t.astype(jnp.float32), out)
            # 'loss' scope: CE (+aux) ops separate from the backbone's
            # layers in the device-time waterfall (telemetry/profile.py).
            def loss_of(lbls):
                """``(loss, counters)`` against ``lbls``: the looped
                model's expectation over exits, else CE (+aux)."""
                if isinstance(out, ExitOutputs):
                    with jax.named_scope("exit_loss"):
                        return exit_expected_loss(
                            out, lbls, class_weights=class_weights,
                            mask=mask, label_smoothing=smoothing,
                            entropy_weight=exit_beta)
                return classification_loss(
                    out, lbls, class_weights=class_weights, mask=mask,
                    aux_weight=aux_w, label_smoothing=smoothing,
                    impl="fused" if optim_cfg.fused_loss
                    else "reference", mesh=mesh), {}

            with jax.named_scope("loss"):
                loss, counters = loss_of(labels)
                counters = {**counters,
                            **_sown_counters(mutated.get("counters", {}))}
                if labels_mix is not None:
                    loss = lam * loss + (1.0 - lam) * loss_of(labels_mix)[0]
                routers = _moe_router_stats(mutated.get("intermediates",
                                                        {}))
                if routers and model_cfg.moe_aux_weight:
                    from tpuic.models.moe import switch_aux_loss
                    aux = sum(switch_aux_loss(p, o, mask)
                              for p, o in routers)
                    loss = loss + (model_cfg.moe_aux_weight * aux
                                   / len(routers))
            if isinstance(out, ExitOutputs):
                logits = out.logits[-1]     # accuracy reads the last pass
            else:
                logits = out[0] if isinstance(out, tuple) else out
            return loss, (mutated.get("batch_stats", state.batch_stats),
                          logits, counters)

        if loss_scale != 1.0:
            # Static loss scaling (OptimConfig.loss_scale): backward runs
            # on the scaled loss, then both are unscaled — numerically a
            # no-op in exact arithmetic; in bf16 it lifts tiny cotangents
            # over underflow. Overflow => non-finite grads => the skip
            # guard below drops the step.
            def scaled_loss_fn(params):
                loss, aux = loss_fn(params)
                return loss * loss_scale, aux
            (loss, (new_stats, logits, counters)), grads = \
                jax.value_and_grad(scaled_loss_fn, has_aux=True)(state.params)
            inv = 1.0 / loss_scale
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
        else:
            (loss, (new_stats, logits, counters)), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        grad_norm = optax.global_norm(grads)

        @jax.named_scope("optimizer_update")
        def _apply_update(st: TrainState) -> TrainState:
            new_state = st.apply_gradients(grads=grads).replace(
                batch_stats=new_stats)
            from tpuic.runtime import faults as _faults
            if _faults.fire("bf16_master_truncate"):
                # Seeded mixed-precision bug (trace-time inject, baked
                # into the compiled step): master weights round-trip
                # through bf16 every update — exactly the no-f32-master
                # mistake the scripts/bf16_parity.py convergence gate
                # exists to catch. Never armed outside the gate's
                # --expect-fail arm.
                new_state = new_state.replace(params=jax.tree.map(
                    lambda p: p.astype(jnp.bfloat16).astype(p.dtype),
                    new_state.params))
            if optim_cfg.ema_decay > 0 and st.ema_params is not None:
                d = optim_cfg.ema_decay
                new_ema = jax.tree.map(lambda e, p: d * e + (1.0 - d) * p,
                                       st.ema_params, new_state.params)
                k = max(1, optim_cfg.grad_accum_steps)
                if k > 1:
                    # Under gradient accumulation params move only every
                    # K-th micro-step (optax.MultiSteps); advancing the EMA
                    # on the other K-1 would compound the decay to d^K per
                    # real update. Hold it between real updates instead.
                    is_update = ((st.step + 1) % k) == 0
                    new_ema = jax.tree.map(
                        lambda ne, e: jnp.where(is_update, ne, e),
                        new_ema, st.ema_params)
                new_state = new_state.replace(ema_params=new_ema)
            return new_state

        if optim_cfg.skip_nonfinite:
            # Non-finite step guard (docs/robustness.md): keep the update
            # only when loss AND global grad norm are finite; otherwise the
            # state passes through UNCHANGED (params, opt_state, BN stats,
            # EMA, step counter) — one poisoned batch costs one skipped
            # step, not the run. One compiled program either way, so a NaN
            # batch causes zero recompiles.
            #
            # Implemented as a per-leaf select, NOT lax.cond: a cond whose
            # skip branch passes donated inputs through to the outputs hits
            # a buffer-aliasing bug in executables deserialized from the
            # persistent compilation cache on this container's jax 0.4.37
            # CPU backend — after a checkpoint restore, steps through the
            # disk-cached executable read corrupted buffers (NaN loss on
            # finite data; reproduced and bisected: cache+donate+cond is
            # the exact trigger, any two of the three are fine). The select
            # computes the update unconditionally and discards it on skip —
            # a few elementwise ops on the update path, negligible next to
            # fwd/bwd. The select's own pass-through aliasing still upsets
            # cache-deserialized CPU executables intermittently, so the
            # donate gate above also applies (cpu + cache + guard =>
            # donate=False); TPU and cache-less runs are untouched.
            finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            updated = _apply_update(state)
            new_state = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old), updated, state)
            if state.skip_count is not None:
                # Consecutive-skip streak, in-graph (train/state.py): the
                # Trainer reads it via the deferred metrics drain and rolls
                # back past RunConfig.skip_threshold.
                new_state = new_state.replace(skip_count=jnp.where(
                    finite, 0, state.skip_count + 1).astype(jnp.int32))
        else:
            new_state = _apply_update(state)
        with jax.named_scope("step_metrics"):
            acc = accuracy(logits, labels)
            if mask is not None:
                m = mask.astype(jnp.float32)
                acc_mean = jnp.sum(acc * m) / jnp.maximum(jnp.sum(m), 1.0)
            else:
                acc_mean = jnp.mean(acc)
        # ``counters``: what the family counts in a step, by the names it
        # gives them: a looped model's exit statistics (loss_pass<t>,
        # exit_p<t>, exit_expected_pass, exit_entropy), a routed layer's
        # pairs and load; empty otherwise. Every key of the metrics that
        # is not in STEP_METRICS is one of them.
        metrics = {"loss": loss, "accuracy": acc_mean,
                   "grad_norm": grad_norm, **counters}
        if optim_cfg.skip_nonfinite:
            metrics["skipped"] = 1.0 - finite.astype(jnp.float32)
            if new_state.skip_count is not None:
                metrics["skip_count"] = new_state.skip_count
        if lr_schedule is not None:
            metrics["lr"] = lr_schedule(state.step)
        return new_state, metrics

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,) if donate else ())
    repl, data = _replicated(mesh), _batch_shardings(mesh)
    st = state_sharding if state_sharding is not None else repl
    return jax.jit(
        train_step,
        in_shardings=(st, data),
        out_shardings=(st, repl),
        donate_argnums=(0,) if donate else (),
    )


def make_eval_step(optim_cfg: OptimConfig, model_cfg: ModelConfig,
                   mesh: Optional[Mesh] = None, state_sharding=None,
                   per_sample: bool = False,
                   per_class: bool = False) -> Callable:
    """Returns jitted ``eval_step(state, batch) -> metrics``.

    metrics: {'correct': Σ 0/1 over valid, 'count': Σ mask,
    'loss_num': Σ w·nll, 'loss_den': Σ w}. Summing each across batches and
    dividing on host gives the exact global val accuracy (reference
    train.py:92, minus the pickle gather and the sampler-padding
    double-count) and the exact global weighted CE (numerator and
    denominator accumulated separately so batch composition can't skew the
    weighted mean).

    per_sample=True adds ``wrong``: the [global_batch] 0/1
    misclassification vector. Its output sharding is replicated, so under a
    mesh GSPMD materializes it with an all-gather over the ``data`` axis —
    the fixed-shape, ICI-ridden redesign of the reference's pickle-based
    ragged all_gather of per-sample results (ddp_utils.py:16-56): every
    host ends up with the full global vector and can map positions back to
    image ids through the (host-replicated) epoch order
    (tpuic.data.Loader attaches ``batch.indices``).

    per_class=True adds ``confusion``: the [C, C] count matrix
    (rows = true class, cols = predicted), computed as a one-hot
    contraction over the batch dim — a fixed-shape matmul GSPMD reduces
    over the ``data`` axis like every other eval sum (no ragged
    per-class gathers). Summed across batches it yields exact global
    per-class accuracy (diagonal / row sums).
    """
    class_weights = (jnp.asarray(optim_cfg.class_weights, jnp.float32)
                     if optim_cfg.class_weights else None)

    def eval_step(state: TrainState, batch):
        images, labels = batch["image"], batch["label"]
        mask = batch.get("mask")
        m = (mask.astype(jnp.float32) if mask is not None
             else jnp.ones(labels.shape, jnp.float32))
        # Validation (and thus 'best' checkpoint selection) uses the EMA
        # weights when the recipe maintains them (state.inference_params).
        variables = {"params": state.inference_params,
                     "batch_stats": state.batch_stats}
        logits = state.apply_fn(variables, images, train=False)
        with jax.named_scope("eval_metrics"):
            acc = accuracy(logits, labels)
            loss = classification_loss(logits, labels,
                                       class_weights=class_weights, mask=m)
        if class_weights is not None:
            w = jnp.sum(jax.nn.one_hot(labels, logits.shape[-1],
                                       dtype=jnp.float32)
                        * class_weights[None, :], axis=-1) * m
        else:
            w = m
        loss_den = jnp.sum(w)
        out = {"correct": jnp.sum(acc * m), "count": jnp.sum(m),
               "loss_num": loss * loss_den, "loss_den": loss_den}
        if logits.shape[-1] > 5:
            # Top-5 (the ImageNet convention; meaningless below 6 classes).
            out["correct5"] = jnp.sum(topk_accuracy(logits, labels, 5) * m)
        if per_sample:
            out["wrong"] = (1.0 - acc) * m
        if per_class:
            n_cls = logits.shape[-1]
            oh_true = jax.nn.one_hot(labels, n_cls,
                                     dtype=jnp.float32) * m[:, None]
            oh_pred = jax.nn.one_hot(jnp.argmax(logits, axis=-1), n_cls,
                                     dtype=jnp.float32)
            out["confusion"] = jnp.einsum("bt,bp->tp", oh_true, oh_pred)
        return out

    if mesh is None:
        return jax.jit(eval_step)
    repl, data = _replicated(mesh), _batch_shardings(mesh)
    st = state_sharding if state_sharding is not None else repl
    return jax.jit(eval_step, in_shardings=(st, data), out_shardings=repl)
