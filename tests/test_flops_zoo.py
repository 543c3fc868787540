"""Analytic-FLOPs table vs the compiler, across the whole model zoo.

FWD_FLOPS_PER_IMAGE feeds every in-band MFU number; nothing validated
it beyond the single model a bench/profile run happened to load.  That
let literature GMAC counts pasted as FLOPs (2x low) sit in the table
for the entire zoo — the resnet18-cifar instance surfaced as a 43%
drift in PR 10, and the PR-16 sweep below caught the SAME bug in every
other row (plus a vit-tiny entry copied from DeiT-Ti literature onto a
test-scale model with ~5x that cost).  This file makes the next such
paste fail CI instead of skewing baselines for three PRs: each entry
is compared against XLA's own cost analysis of a forward-only compile
at the canonical shape.

Compile-only: params are abstract (jax.eval_shape), nothing executes,
so even the big models are just a CPU compile.  The tier-1 set covers
all four families; the full-fat ends (resnet101/152, b3/b7, the
16-patch and large ViTs) ride in -m slow.
"""

import jax
import jax.numpy as jnp
import pytest

from tpuic.models import create_model
from tpuic.telemetry.goodput import (FWD_FLOPS_PER_IMAGE, PEAK_FLOPS,
                                     PEAK_FLOPS_F32, check_flops_drift,
                                     cost_analysis_dict, peak_flops)

# Forward-only drift bound.  10% is check_flops_drift's own warning
# threshold; resnet18-cifar carries a documented 16%: its entry is
# tuned so the TRAIN-side drift (what the profile smoke asserts) sits
# at ~7% — the compiled backward runs ~2.7x forward, so the 3x-forward
# analytic overshoots the forward alone by more than the whole step.
_DEFAULT_TOL = 0.10
_TOL = {"resnet18-cifar": 0.16}

_TIER1 = ["resnet18-cifar", "resnet18", "resnet34", "resnet50",
          "inceptionv3", "efficientnet-b0", "vit-tiny", "vit-b32"]
_BIG = ["resnet101", "resnet152", "efficientnet-b3", "efficientnet-b7",
        "vit-s16", "vit-b16", "vit-l16", "vit-l32"]


def _compiled_fwd_flops(name: str, size: int, batch: int = 2) -> float:
    """XLA's FLOP count for one eval forward at the canonical shape.

    Abstract init + lower + compile only — no param materialization, no
    execution — so this stays cheap enough for tier-1 on CPU.
    """
    model = create_model(name, 10, dtype="float32")
    x = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda rng, xx: model.init(rng, xx, train=False),
        jax.random.key(0), x)
    compiled = jax.jit(
        lambda v, xx: model.apply(v, xx, train=False)).lower(
            variables, x).compile()
    return float(cost_analysis_dict(compiled).get("flops", 0.0))


def _assert_table_row_tracks_compiler(name: str) -> None:
    gflops, size = FWD_FLOPS_PER_IMAGE[name]
    compiled = _compiled_fwd_flops(name, size)
    assert compiled > 0.0, f"no cost analysis for {name}"
    tol = _TOL.get(name, _DEFAULT_TOL)
    warned = []
    drift = check_flops_drift(name, size, 2, compiled, train=False,
                              tol=tol, warn=warned.append)
    assert drift is not None
    assert not warned, warned
    assert drift <= tol, (
        f"{name}: table {gflops:.3e}/img vs compiled "
        f"{compiled / 2:.3e}/img — drift {drift:.1%} > {tol:.0%}; a 2x "
        "drift means a GMAC count was pasted as FLOPs again")


@pytest.mark.parametrize("name", _TIER1)
def test_flops_table_tracks_compiler(name):
    _assert_table_row_tracks_compiler(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", _BIG)
def test_flops_table_tracks_compiler_big(name):
    _assert_table_row_tracks_compiler(name)


def test_zoo_sweep_covers_every_table_row():
    """A new table entry must join one of the sweep sets — an
    unexercised row is exactly how the 2x paste survives."""
    assert set(_TIER1) | set(_BIG) == set(FWD_FLOPS_PER_IMAGE)


# -- dtype-aware peak-FLOPS table (the MFU denominator) ----------------------

def test_peak_flops_dtype_ladder():
    """f32 peak is half the bf16 MXU rate on every TPU generation; the
    CPU nominal stays 1e12 for both (CI determinism — XLA CPU has no
    published dtype-split peak).  An f32 run judged against the bf16
    peak would read as half its true MFU."""
    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    for kind, bf16_peak in PEAK_FLOPS.items():
        want = bf16_peak if kind == "cpu" else bf16_peak / 2.0
        assert PEAK_FLOPS_F32[kind] == want
        assert peak_flops(_Dev(kind), "bf16") == bf16_peak
        assert peak_flops(_Dev(kind), "f32") == want
    # default dtype arg is the historical bf16 behaviour
    v5e = _Dev("TPU v5 lite")
    assert peak_flops(v5e) == peak_flops(v5e, "bfloat16") == 197e12
    assert peak_flops(v5e, "float32") == 98.5e12
    # unknown device kind: an error, never a made-up peak; None reads
    # the nominal cpu row (CI)
    with pytest.raises(ValueError, match="unknown device kind 'QPU v1'"):
        peak_flops(_Dev("QPU v1"), "bf16")
    assert peak_flops(None, "f32") == 1e12
    with pytest.raises(ValueError, match="dtype"):
        peak_flops(v5e, "fp8")
