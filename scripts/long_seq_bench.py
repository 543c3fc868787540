#!/usr/bin/env python
"""Long-sequence attention bench: dense vs Pallas flash at growing N.

The round-3 smoke (perf/pallas_smoke.json) showed flash LOSES to dense at
ViT-B's N=197 — its value is O(N*D) HBM at long sequence lengths. This
script quantifies the crossover on the real chip: ViT-B/16 train step at
224/384/512px (N = 197/577/1025 tokens) with attention='dense' vs 'flash',
recording step time and peak memory. Writes perf/long_seq.json.

Each (size, attention) config runs in its OWN subprocess:
``peak_bytes_in_use`` is a process-lifetime high-water mark, so measuring
several configs in one process would floor every later number at the
earlier peak and erase exactly the dense-vs-flash memory difference this
bench exists to show.

Usage: python scripts/long_seq_bench.py [--sizes 224,384,512] [--batch 32]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def measure(size: int, attention: str, batch: int, n_steps: int = 10,
            remat: bool = False, remat_policy: str = "dots"):
    import jax

    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()

    from tpuic.config import ModelConfig, OptimConfig
    from tpuic.data.synthetic import synthetic_batch
    from tpuic.models import create_model_from_config
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import create_train_state
    from tpuic.train.step import make_train_step

    # remat: at N >= 2k the NON-attention activations (qkv/mlp intermediates
    # x depth) alone exceed HBM at useful batch sizes; rematerializing them
    # keeps the measurement about the attention memory term, which is the
    # dense-vs-flash difference this bench exists to isolate.
    mcfg = ModelConfig(name="vit-b16", num_classes=1000, dtype="bfloat16",
                       attention=attention, remat=remat,
                       remat_policy=remat_policy)
    ocfg = OptimConfig(optimizer="sgd", learning_rate=0.1, class_weights=(),
                       milestones=())
    # create_model_from_config, NOT create_model: the model-level remat
    # policies ('attention' -> remat_core, 'blocks' -> remat_blocks) only
    # flow from the CONFIG path; building the model directly would
    # silently measure step-level remat only (XLA's own auto-remat then
    # masks the difference at memory-pressure shapes).
    model = create_model_from_config(mcfg)
    state = create_train_state(model, make_optimizer(ocfg),
                               jax.random.key(0), (batch, size, size, 3))
    data = synthetic_batch(batch, size, mcfg.num_classes)
    data = {k: jax.device_put(v) for k, v in data.items()}
    step = make_train_step(ocfg, mcfg, None, donate=True)
    state, m = step(state, data)
    float(m["loss"])  # force completion
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, m = step(state, data)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / n_steps
    mem = None
    try:
        ms = jax.devices()[0].memory_stats()
        mem = round(ms.get("peak_bytes_in_use", 0) / (1 << 20))
    except Exception:
        pass
    n_tokens = (size // 16) ** 2 + 1
    return {"size": size, "tokens": n_tokens, "attention": attention,
            "remat": remat, "remat_policy": remat_policy if remat else None,
            "step_ms": round(1000 * dt, 2), "peak_mem_mb": mem,
            "images_per_sec": round(batch / dt, 1),
            "platform": jax.devices()[0].platform,
            "device": getattr(jax.devices()[0], "device_kind", "?")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="224,384,512")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize encoder activations (needed to "
                         "reach N>=2k at useful batch sizes)")
    ap.add_argument("--remat-policy", default="dots",
                    choices=("dots", "attention", "blocks"),
                    help="what --remat recomputes (ModelConfig.remat_policy;"
                         " 'blocks' = per-encoder-block, the long-context "
                         "memory mode)")
    ap.add_argument("--out", default=os.path.join(_REPO, "perf",
                                                  "long_seq.json"))
    ap.add_argument("--_child", nargs=2, metavar=("SIZE", "ATTENTION"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._child:
        size, attention = int(args._child[0]), args._child[1]
        print(json.dumps(measure(size, attention, args.batch,
                                 remat=args.remat,
                                 remat_policy=args.remat_policy)),
              flush=True)
        return 0

    rows = []
    configs = [(size, attention)
               for size in (int(s) for s in args.sizes.split(","))
               for attention in ("dense", "flash")]
    for size, attention in configs:
        # Popen + terminate-then-kill rather than subprocess.run: run's
        # timeout SIGKILLs immediately; SIGTERM first gives the child a
        # grace window to close the backend and release the chip.
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--batch", str(args.batch)]
            + (["--remat"] if args.remat else [])
            + ["--remat-policy", args.remat_policy]
            + ["--_child", str(size), attention],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=_REPO)
        try:
            stdout, stderr = proc.communicate(timeout=900)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                # communicate, not wait: the pipes must keep draining or a
                # child with a full stderr buffer blocks in write() and
                # burns the grace window.
                proc.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()
            row = {"size": size, "attention": attention,
                   "error": "timed out after 900s"}
            rows.append(row)
            print(json.dumps(row), flush=True)
            continue
        row = None
        for line in reversed((stdout or "").strip().splitlines()):
            try:
                row = json.loads(line)
                break
            except (json.JSONDecodeError, ValueError):
                continue
        if row is None:
            # Prefer the XLA OOM line (the reason this bench exists is to
            # find it) over the generic traceback tail.
            import re as _re
            err_lines = [_re.sub(r"\x1b\[[0-9;]*m", "", ln)
                         for ln in (stderr or "").strip().splitlines()]
            oom = [ln for ln in err_lines if "Ran out of memory" in ln]
            tail = (oom[0].split("error.", 1)[-1].strip() if oom
                    else " | ".join(err_lines[-2:]))
            row = {"size": size, "attention": attention, "oom": bool(oom),
                   "error": f"rc={rc}: {tail[:300]}"}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {"batch": args.batch, "model": "vit-b16", "remat": args.remat,
           "remat_policy": args.remat_policy if args.remat else None,
           "rows": rows}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
