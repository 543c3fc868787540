#!/usr/bin/env python
"""Quantization accuracy gate (tier-1 CI; docs/performance.md,
"Quantized serving").

The serve dtype ladder's contract is that a quantized rung moves
predictions by at most the committed epsilon
(``tpuic.quant.DEFAULT_EPSILON``) on the pinned synthetic eval set.
This script proves it BOTH ways, the same bidirectional discipline as
the perf-regression and roofline gates:

- clean: the bf16 and int8 rungs of a pinned seeded model must pass
  (top-1 agreement with fp32 >= 1 - epsilon);
- ``--corrupt --expect-fail``: the same int8 rung built from a seeded
  weight corruption (``quant.corrupt_variables``) must FAIL the gate —
  a gate that cannot fire is decoration.

Everything is seeded (model init, eval images, corruption), so the CI
verdict is reproducible.

    python scripts/quant_gate.py
    python scripts/quant_gate.py --corrupt --expect-fail
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _force_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="resnet18-cifar")
    p.add_argument("--num-classes", type=int, default=10)
    p.add_argument("--size", type=int, default=24)
    p.add_argument("--eval-n", type=int, default=256)
    p.add_argument("--corrupt", action="store_true",
                   help="build the int8 rung from seeded-corrupted "
                        "weights (the gate-can-fire arm)")
    p.add_argument("--expect-fail", action="store_true",
                   help="exit 0 IFF the gate fails (CI's bidirectional "
                        "proof)")
    args = p.parse_args(argv)

    _force_cpu()
    import jax
    import jax.numpy as jnp

    from tpuic import quant
    from tpuic.models import create_model

    model = create_model(args.model, args.num_classes, dtype="float32")
    variables = model.init(
        jax.random.key(0),
        jnp.zeros((1, args.size, args.size, 3), jnp.float32), train=False)
    imgs = quant.eval_images(args.eval_n, args.size)
    floor = 1.0 - quant.DEFAULT_EPSILON

    variants = quant.serve_variants(model, variables,
                                    ("fp32", "bf16", "int8"),
                                    normalize=True)
    ref_fwd, ref_vars = variants["fp32"]
    ref = jax.jit(ref_fwd)

    failed = []
    for tag in ("bf16", "int8"):
        fwd, qv = variants[tag]
        if args.corrupt and tag == "int8":
            # The must-fail arm: quantize weights that no longer match
            # the fp32 reference — the exact bug class (a broken
            # quantization pass, a stale scale tree) the gate exists
            # to catch.
            qv = quant.quantize_variables(
                quant.corrupt_variables(variables, seed=0))
        agree = quant.top1_agreement(ref, ref_vars, jax.jit(fwd), qv, imgs)
        verdict = "ok" if agree >= floor else "FAILED"
        print(f"[quant-gate] {tag:<5} top-1 agreement {agree:.4f} "
              f"(floor {floor:.4f}, epsilon {quant.DEFAULT_EPSILON}) "
              f"{verdict}")
        if agree < floor:
            failed.append(tag)

    if args.expect_fail:
        if failed:
            print(f"[quant-gate] expected failure observed on "
                  f"{', '.join(failed)} — the gate can fire "
                  "(bidirectional proof OK)")
            return 0
        print("[quant-gate] ERROR: seeded corruption did NOT trip the "
              "gate — the gate is decoration", file=sys.stderr)
        return 2
    if failed:
        print(f"[quant-gate] REGRESSION: rung(s) {', '.join(failed)} "
              f"moved top-1 past the committed epsilon", file=sys.stderr)
        return 2
    print("[quant-gate] clean: every rung within epsilon")
    return 0


if __name__ == "__main__":
    sys.exit(main())
