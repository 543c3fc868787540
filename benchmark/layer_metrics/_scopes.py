"""What the readers of device time by scope share: the run's trace read by
the program's own reader, ``tpuic.telemetry.profile.parse_trace``, with the
scope maps of the programs the run registered (the train step, the
loader's device prep), on the lowest-numbered device, over the same window
and count of steps as the other device metrics.

Read once a run: the first reader to ask builds the maps (lowering the
registered functions on their abstract arguments finds JAX's executables
in memory: nothing compiles) and parses the trace; the others take the
same result. The attribution, every op with its program, scope path and
milliseconds a step, and under ``inferred`` the part of each scope charged
to ops whose own metadata named none, is written beside the trace as
``scopes.json``, and a summary goes to standard error. Nothing where the
run has no device trace or the program registers no programs (a program
older than the registry).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

from benchmark.layer_metrics import _banded

PREP = "input_prep"
_read: dict = {}            # xplane path -> attribution (None: nothing)


def attribution(obs) -> Optional[dict]:
    run = _banded.traced_cell(obs)
    if run is None:
        return None
    path = run[0]
    if path not in _read:
        _read[path] = _attribute(path)
    return _read[path]


def _attribute(path: str) -> Optional[dict]:
    from tpuic.telemetry import profile
    programs = getattr(profile, "programs", None)
    if programs is None or not programs.roles():
        return None
    from tpuic.telemetry.events import (install_jax_compile_listener,
                                        subscribe)
    install_jax_compile_listener()
    compiles: list = []
    unsubscribe = subscribe(
        lambda ev: compiles.append(ev.data.get("key")), kinds=("compile",))
    t0 = time.perf_counter()
    try:
        maps = programs.scope_maps()
    finally:
        unsubscribe()
    t1 = time.perf_counter()
    res = profile.parse_trace(path, maps=maps)
    if res is None:
        return None
    res["maps_s"] = t1 - t0
    res["maps_backend_compiles"] = compiles.count("backend_compile_duration")
    res["parse_s"] = time.perf_counter() - t1
    with open(os.path.join(os.path.dirname(path), "scopes.json"), "w") as f:
        json.dump(res, f)
    step = res["partition"].get("step", {})
    inferred = {how: _rounded(v) for how, v in res["inferred"].items()}
    print(f"[scopes] maps {res['maps_s']:.2f} s "
          f"({res['maps_backend_compiles']} backend compiles), parse "
          f"{res['parse_s']:.2f} s; {res['steps']} steps; programs "
          f"{_rounded(res['programs'])}; unmapped ops {res['unmapped']}; "
          f"idle {_rounded(res['idle'])}; step by scope path "
          f"{_rounded(dict(list(step.items())[:12]))}; scopes "
          f"{_rounded(dict(list(res['scopes'].items())[:24]))}; of them "
          f"charged to ops without a scope of their own {inferred}",
          file=sys.stderr, flush=True)
    return res


def _rounded(d: dict) -> dict:
    return {k: round(v, 4) for k, v in d.items()}


def scope_ms(obs, scope: str) -> Optional[float]:
    """Device ms a step of the ops under ``scope``; None where none ran."""
    res = attribution(obs)
    return None if res is None else res["scopes"].get(scope)
