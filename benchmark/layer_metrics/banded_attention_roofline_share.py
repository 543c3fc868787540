"""Kernels and XLA ops: the banded attention core's share of its roofline,
which is the chip's bfloat16 peak (``_banded.py`` says what the byte side
would be and why it does not bind): the core's required FLOPs a step, from
the configuration and the batch alone (pairs inside the mask x heads x two
contractions x head size x 2, forward; x 3 for the step), over the device
time a step of the ops whose names carry ``banded_attention_`` times the
peak, in percent. Recomputation under remat and the masked halves of
diagonal tiles add time and no required work, so it cannot pass 100.
Nothing where the trace holds no such op."""

from benchmark.layer_metrics import _banded
from benchmark.peaks import peaks_for


def read(obs):
    run = _banded.traced_cell(obs)
    if run is None:
        return None
    path, config, traffic = run
    flops = _banded.core_flops_per_step(config, int(traffic["per_chip_batch"]))
    seconds = _banded.kernel_seconds_per_step(path)
    if not flops or not seconds:
        return None
    peak = peaks_for(obs.spans["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (seconds * peak)
