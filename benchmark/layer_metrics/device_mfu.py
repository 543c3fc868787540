"""Kernels and XLA ops: FLOPs one step requires on one chip (from shapes,
``benchmark/flops.py``) over the step's device busy time times the chip's
published bf16 peak. Busy time, not wall time: idle time is
``device_idle_share``'s to report."""

from benchmark.layer_metrics import device_step_ms
from benchmark.peaks import peaks_for


def read(obs):
    step_ms = device_step_ms.read(obs)
    flops = obs.spans.get("flops_per_step_per_chip")
    if step_ms is None or not flops:
        return None
    peak = peaks_for(obs.spans["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (step_ms * 1e-3 * peak)
