"""Step program: device busy time (union of op intervals on device 0)
per execution of the step program, from the trace."""

from benchmark.layer_metrics._common import device0


def read(obs):
    dev = device0(obs)
    if dev is None or not dev["steps"]:
        return None
    return 1e3 * dev["busy_s"] / dev["steps"]
