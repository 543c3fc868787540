"""Device: what a step holds on the chip beyond the state that persists
between steps. ``memory_peak_bytes`` of the benchmark's spans less the
``param_bytes + opt_state_bytes`` the program's first ``trainer.state_init``
span states: gradients, compute-dtype copies of the weights, the residuals
saved for the backward pass and workspace. It is what a loop over shared
weights multiplies and what a rematerialisation policy trades against
time. Nothing where the allocator reports no peak (a CPU) or the program's
span lacks the two attributes."""

from benchmark.layer_metrics._spans import first, train_ledger


def read(obs):
    peak = obs.spans.get("memory_peak_bytes")
    records = train_ledger(obs)
    if peak is None or records is None:
        return None
    init = first(records, "trainer.state_init")
    attrs = (init or {}).get("attrs") or {}
    if "param_bytes" not in attrs or "opt_state_bytes" not in attrs:
        return None
    return (peak - attrs["param_bytes"] - attrs["opt_state_bytes"]) / 2**30
