"""Trainer loop: the share of the device's idle time in the window, in
percent, that the trace's labels leave unnamed (``_scopes.py``, through
``tpuic.telemetry.profile.attribute_device``). Each idle gap takes one
label, by its midpoint: ``inside <program>`` where the midpoint lies inside
an execution (the program waiting between two of its own ops),
``queued <program>`` where the host had dispatched the next execution
before the gap began (the device launching work it already had), else the
innermost ``tpuic.*`` host annotation over the midpoint (the step loop's
``tpuic.step.next`` / ``dispatch`` / ``drain`` / ``end``, the program's
spans); unnamed is a gap with none of these. So it reads how much of the
idle the program's tracing accounts for, not how much idle there is: a
change of speed moves it only where it changes which labels cover the
gaps, and one long gap (the epoch boundary's, where a traced slice holds
one) decides it. Nothing where the trace was not read; 0 where the device
never idled."""

from benchmark.layer_metrics import _scopes


def read(obs):
    res = _scopes.attribution(obs)
    if res is None:
        return None
    from tpuic.telemetry.profile import UNLABELLED
    idle = sum(res["idle"].values())
    unlabelled = res["idle"].get(UNLABELLED, 0.0)
    return 100.0 * unlabelled / idle if idle > 0 else 0.0
