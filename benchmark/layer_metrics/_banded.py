"""What the readers of a banded attention core share: the core's required
operations a step from the configuration (independent of what implements
the core), and the device time of the ops whose names carry
``banded_attention_`` in the run's own trace.

The required work of a causal core over ``T`` positions with a window
``W`` (none: ``T``): query ``i`` sees ``min(i + 1, W)`` keys, so an image
has ``pairs(T, W) = sum over i`` of that; each pair costs a head two
contractions of ``head_dim`` multiply-adds (``q.k`` and ``p v``), forward;
a training step is three forwards' worth (``benchmark/flops.py``'s
convention: the backward pass twice the forward; recomputation not
counted). Bytes, for the record: a layer's forward reads ``q``, ``k`` and
``v`` once and writes ``o`` (at 4 images of 4,096 tokens, 32 + 4 + 4 + 32
heads of 128 in bfloat16: 302 MB, 0.37 ms at 819 GB/s) where its required
contractions take 1.6 ms (sliding) or 3.6 ms (full) at the bfloat16 peak:
the core at head size 128 is bound by compute, four to ten times over, and
the share is taken of the peak alone.
"""

from __future__ import annotations

import os
from typing import Optional

KERNEL_TAG = "banded_attention_"


def pairs_seen(tokens: int, window: Optional[int] = None) -> int:
    """Unmasked (query, key) pairs of one head of one image."""
    w = tokens if window is None else min(window, tokens)
    return w * (w + 1) // 2 + (tokens - w) * w


def core_macs_per_token_per_layer(config: dict, kind: str) -> float:
    """Forward multiply-adds of the core a token, in a layer of ``kind``
    (an entry of ``layer_types``)."""
    tokens = (int(config["image_size"]) // int(config["patch"])) ** 2
    window = (int(config["sliding_window"])
              if kind == "sliding_attention" else None)
    return (2.0 * int(config["num_attention_heads"]) * int(config["head_dim"])
            * pairs_seen(tokens, window) / tokens)


def core_flops_per_step(config: dict, batch: int) -> Optional[float]:
    """Required FLOPs of the core in one training step of ``batch``
    images; None for a configuration that has no such core."""
    kinds = config.get("layer_types")
    if not kinds or "sliding_window" not in config:
        return None
    tokens = (int(config["image_size"]) // int(config["patch"])) ** 2
    macs = sum(core_macs_per_token_per_layer(config, kind)
               for kind in kinds[:int(config["num_hidden_layers"])])
    return 3.0 * 2.0 * macs * tokens * batch


def traced_cell(obs):
    """``(xplane path, configuration, traffic)`` of the traced run that
    ``obs`` comes from: the harness keeps a run's trace under
    ``.bench_cache/work/<cell>/trace`` and empties that directory when the
    run starts, so the newest trace is this run's and its path names the
    cell. None where the run has no device trace or the cell is not one of
    ``BENCHMARK.json``'s."""
    if not obs.trace or not obs.trace.get("devices"):
        return None
    from benchmark import harness, trace_reduce
    work = os.path.join(harness.CACHE_DIR, "work")
    path = trace_reduce.find_xplane(work)
    if path is None:
        return None
    cell = os.path.relpath(path, work).split(os.sep)[0]
    try:
        resolved = harness.resolve_cell(harness.load_spec(), cell)
    except KeyError:
        return None
    return path, resolved["config"], resolved["traffic"]


def kernel_seconds_per_step(path: str) -> Optional[float]:
    """Device seconds a step of the ops named ``banded_attention_*`` on
    the lowest-numbered device of the trace at ``path``, over the same
    window and the same count of steps as the run's other device metrics
    (``trace_reduce.reduce_device`` on those ops alone); None where the
    trace holds no such op (a program without the kernel)."""
    from benchmark import trace_reduce
    raw = trace_reduce.read_xplane(path)["devices"]
    if not raw:
        return None
    dev = raw[min(raw)]
    ops = [e for e in dev["ops"] if KERNEL_TAG in trace_reduce.op_name(e[0])]
    red = trace_reduce.reduce_device(dev["modules"], ops)
    if red is None or not red["steps"] or not red["busy_s"]:
        return None
    return red["busy_s"] / red["steps"]
