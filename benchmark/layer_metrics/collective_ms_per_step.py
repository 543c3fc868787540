"""Mesh and sharding: time covered by collective ops (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; asynchronous
ones from start to done) on device 0, per step."""

from benchmark.layer_metrics._common import device0


def read(obs):
    dev = device0(obs)
    if dev is None or not dev["steps"]:
        return None
    return 1e3 * dev["collective_s"] / dev["steps"]
