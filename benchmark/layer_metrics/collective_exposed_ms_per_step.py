"""Mesh and sharding: the part of the collective time during which no
other op ran on device 0, per step."""

from benchmark.layer_metrics._common import device0


def read(obs):
    dev = device0(obs)
    if dev is None or not dev["steps"]:
        return None
    return 1e3 * dev["collective_exposed_s"] / dev["steps"]
