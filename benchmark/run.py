#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip(s) for its whole life and starts no child
that needs them. Set-up (imports, data, construction, compile or cache
load, warm-up) runs first and is reported as ``setup_s``; then the cell's
traffic is measured for ``--seconds``; then results are checked against the
plain reference. The last line of standard output is the one JSON object
the driver reads; everything else is on earlier lines.

There is no fallback: without a TPU of the cell's chip count the command
exits non-zero and prints no result. A CPU rehearsal has to be asked for by
name (``JAX_PLATFORMS=cpu ... --tiny``); it swaps in the ``tiny`` sizes of
the configuration and traffic files, and its device line says so.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)    # `benchmark`, `tpuic` and `train` live here

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec", default="BENCHMARK.json",
                    help="the file that defines the cell, relative to the "
                         "checkout (benchmark/candidates.json: cells built "
                         "and measured but not admitted)")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at the files' tiny sizes; never a "
                         "measurement")
    args = ap.parse_args(argv)

    from benchmark import harness
    resolved = harness.resolve_cell(harness.load_spec(args.spec),
                                    args.workload, tiny=args.tiny)
    chips = int(resolved["cell"]["chips"])
    try:
        import jax
        devices = jax.devices()
    except Exception as e:      # no backend at all
        print(f"benchmark: JAX found no device: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    platform = devices[0].platform
    if platform != "tpu" and not args.tiny:
        print(f"benchmark: JAX found {len(devices)} {platform} device(s) "
              "and no TPU; nothing is measured on another platform "
              "(a rehearsal is JAX_PLATFORMS=cpu ... --tiny)",
              file=sys.stderr)
        return EXIT_NO_DEVICE
    if (len(devices) != chips if platform == "tpu"
            else len(devices) < chips):
        print(f"benchmark: {args.workload} needs {chips} chip(s), JAX "
              f"found {len(devices)}", file=sys.stderr)
        return EXIT_NO_DEVICE
    try:
        import tpuic    # noqa: F401  the system under test
        import train    # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return EXIT_NO_PROGRAM

    ctx = harness.Context(resolved, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), tiny=args.tiny,
                          t_start=T_START)
    ctx.say(f"{args.workload}: {len(devices)} {platform} device(s), "
            f"kind={devices[0].device_kind!r}, seed={args.seed}, "
            f"seconds={args.seconds}, trace={args.trace}"
            + (", TINY REHEARSAL" if args.tiny else ""))
    try:
        result = harness.load_mode(resolved["traffic"]["mode"]).run(ctx)
        line = harness.result_line(ctx, resolved, result)
    finally:
        ctx.compiles.close()
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
