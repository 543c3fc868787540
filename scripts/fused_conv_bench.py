#!/usr/bin/env python
"""Before/after artifact for the Pallas fused conv+BN+ReLU kernel
(tpuic/kernels/conv_bn_relu.py) -> perf/fused_conv_bn.json.

Three views, each labeled with exactly what it is:

- **parity** (measured): max-abs difference of the fused vs unfused
  inference forward per ResNet variant — the numerics contract
  tests/test_kernels.py pins (atol 1e-4 documented; measured ~1e-7 in
  float32, the fused kernel's f32 tap accumulation is *tighter* than a
  bf16 unfused graph).
- **hlo_waterfall_unfused / hlo_waterfall_fused_interpret** (modeled,
  v5e roofline constants): the op-class waterfalls of the two CPU
  lowerings.  CAVEAT, stated in-artifact: the interpret-mode lowering
  materializes every tap slice as a real copy, which Mosaic never does
  (taps are VMEM reads) — the fused CPU waterfall is an artifact of the
  interpreter, not a picture of the TPU program.
- **finding** (the honest one): on this backend XLA ALREADY
  epilogue-fuses the inference BN affine + ReLU into each convolution
  fusion, so the *unfused* forward's elementwise+copy boundary traffic
  is ~0 to begin with (measured and recorded).  The committed
  perf/roofline_baseline.json's elementwise+copy fraction lives in the
  TRAIN step (backward transposes, optimizer), which an inference
  kernel cannot touch.  What the Pallas kernel buys on TPU — explicit
  taps-as-GEMMs MXU layout (the space-to-depth argument applied to
  every block), f32 VMEM accumulation, and one guaranteed output write
  per block independent of XLA's fusion heuristics — is recorded here
  as the per-block **mosaic_boundary** accounting (bytes the kernel's
  contract admits at its boundary vs the activation roundtrips a
  *non*-epilogue-fusing compiler would pay), pending a chip measurement
  (the perf/pallas_smoke.json pattern).

    python scripts/fused_conv_bench.py --out perf/fused_conv_bn.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _force_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()


def np_prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def _waterfall(exe, peak, bw):
    from tpuic.telemetry.goodput import cost_analysis_dict
    from tpuic.telemetry.profile import hlo_waterfall
    try:
        cost = cost_analysis_dict(exe)
    except Exception:
        cost = {}
    wf = hlo_waterfall(exe.as_text(),
                       total_flops=float(cost.get("flops", 0.0)),
                       peak=peak, hbm_bytes_per_s=bw)
    wf.pop("layers", None)
    return wf


def _ew_copy_frac(wf) -> dict:
    cls = wf["classes"]
    ms = sum(c["ms"] for c in cls.values()) or 1.0
    by = sum(c["bytes"] for c in cls.values()) or 1.0
    ew = sum(cls.get(k, {"ms": 0, "bytes": 0})["ms"]
             for k in ("elementwise", "copy"))
    ewb = sum(cls.get(k, {"ms": 0, "bytes": 0})["bytes"]
              for k in ("elementwise", "copy"))
    return {"ms_frac": round(ew / ms, 4), "bytes_frac": round(ewb / by, 4)}


def _mosaic_boundary(variables) -> dict:
    """Structural boundary accounting from the model's real parameter
    shapes: the kernel admits in + weights + affine + ONE output write
    per fused call (the epilogue is VMEM-interior by construction)."""
    import jax

    shapes = []

    def record(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("kernel") and getattr(leaf, "ndim", 0) == 4:
            shapes.append((name, tuple(leaf.shape)))
    jax.tree_util.tree_map_with_path(record, variables["params"])
    w_bytes = sum(4 * int(np_prod(s)) for _, s in shapes)
    return {"fused_calls": len(shapes),
            "weight_bytes_f32": w_bytes,
            "note": ("each fused call bounds its HBM traffic to "
                     "in + weights + affine + ONE output write by "
                     "construction; a non-epilogue-fusing compiler "
                     "pays +2 activation roundtrips (BN, ReLU) per "
                     "call — XLA CPU/TPU inference usually fuses "
                     "these already (see finding), Mosaic makes the "
                     "bound structural rather than heuristic")}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--models", default="resnet18-cifar,resnet50")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--out", default=os.path.join("perf",
                                                 "fused_conv_bn.json"))
    args = p.parse_args(argv)

    _force_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuic.models import create_model
    from tpuic.telemetry.goodput import HBM_GBPS, PEAK_FLOPS

    # Model the part the kernel targets: v5e roofline constants, where
    # bandwidth-bound elementwise traffic actually costs (the CPU
    # constants drown it under a slow nominal matmul peak).
    peak, bw = PEAK_FLOPS["TPU v5e"], HBM_GBPS["TPU v5e"] * 1e9

    out = {"metric": "fused_conv_bn_relu_parity_and_waterfalls",
           "batch": args.batch, "roofline_constants": "TPU v5e (modeled)",
           "models": {}}
    for name in args.models.split(","):
        name = name.strip()
        size = 32 if "cifar" in name else 64
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (args.batch, size, size, 3)), jnp.float32)
        base = create_model(name, 10, dtype="float32")
        fused = create_model(name, 10, dtype="float32",
                             fused_conv_bn=True)
        v = base.init(jax.random.key(0), x[:1], train=False)
        a = base.apply(v, x, train=False)
        b = fused.apply(v, x, train=False)
        parity = float(jnp.abs(a - b).max())

        exe_u = jax.jit(lambda v, x: base.apply(
            v, x, train=False)).lower(v, x).compile()
        exe_f = jax.jit(lambda v, x: fused.apply(
            v, x, train=False)).lower(v, x).compile()
        wf_u, wf_f = _waterfall(exe_u, peak, bw), _waterfall(exe_f, peak,
                                                             bw)
        out["models"][name] = {
            "image_size": size,
            "parity_max_abs_diff_f32": parity,
            "unfused_ew_copy": _ew_copy_frac(wf_u),
            "fused_interpret_ew_copy": _ew_copy_frac(wf_f),
            "hlo_waterfall_unfused": wf_u,
            "hlo_waterfall_fused_interpret": wf_f,
            "mosaic_boundary": _mosaic_boundary(v),
        }
    out["finding"] = (
        "XLA already epilogue-fuses the inference BN affine + ReLU into "
        "each conv fusion on this backend: the UNFUSED forward's "
        "elementwise+copy boundary fraction is ~0 (see "
        "unfused_ew_copy; resnet50's nonzero number is a single "
        "zero-cost `bitcast` layout reinterpretation around the stem "
        "maxpool that the cost model charges boundary bytes for, not "
        "real traffic), so the waterfall cannot show an "
        "elementwise->matmul shift for the inference graph here. The "
        "committed perf/roofline_baseline.json's elementwise+copy "
        "fraction belongs to the TRAIN step (backward transposes, "
        "optimizer update), out of an inference kernel's reach. The "
        "fused kernel's parity is pinned and its Mosaic boundary bound "
        "is structural (mosaic_boundary.note); the "
        "fused_interpret waterfall is the INTERPRETER's lowering "
        "(materialized tap slices) and does not represent the TPU "
        "program — chip measurement pending, the perf/pallas_smoke.json "
        "pattern.")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items() if k != "models"}))
    for name, m in out["models"].items():
        print(f"[fused-conv] {name}: parity {m['parity_max_abs_diff_f32']:.2e}, "
              f"unfused ew+copy {m['unfused_ew_copy']}, "
              f"fused(interpret) ew+copy {m['fused_interpret_ew_copy']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
