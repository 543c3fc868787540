#!/usr/bin/env python
"""Run a Python entry point in this process, then print its span tree.

    python scripts/span_tree.py [--json OUT.json] train.py --datadir ...
    python scripts/span_tree.py benchmark/run.py --workload <cell> \
        --seed 1 --seconds 20 --trace 1

The program's span ledger (tpuic/telemetry/spans.py) lives in memory, so
the entry point runs here (``runpy``, as ``__main__``) and the ledger is
read when it returns: every span with its start (seconds after the entry
point began, so beside the benchmark's own ``[bench +s]`` lines), its
duration, the part no child covers (``self``) and its attrs.
PERF.md section 5's set-up trees are this script's output on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def format_tree(records: list, self_s: dict, origin: float) -> str:
    ids = {r["id"] for r in records}
    children: dict = {}
    for r in sorted(records, key=lambda r: r["t0"]):
        # a parent that fell out of the ring leaves its children as roots
        children.setdefault(r["parent"] if r["parent"] in ids else None,
                            []).append(r)
    lines = [f"{'span':44s} {'start_s':>9s} {'dur_s':>9s} {'self_s':>9s}"]

    def walk(parent, depth: int) -> None:
        for r in children.get(parent, ()):
            attrs = " ".join(f"{k}={v}" for k, v in r["attrs"].items())
            lines.append(f"{'  ' * depth + r['name']:44s} "
                         f"{r['t0'] - origin:9.3f} {r['t1'] - r['t0']:9.4f} "
                         f"{self_s[r['id']]:9.4f}  {attrs}")
            walk(r["id"], depth + 1)
    walk(None, 0)
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--json", default="",
                    help="also write the ledger's records to this file")
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    ns = ap.parse_args()
    sys.path.insert(0, REPO)
    sys.argv = [ns.script, *ns.args]
    t_launch = time.perf_counter()
    try:
        runpy.run_path(ns.script, run_name="__main__")
        code = 0
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else int(bool(e.code))
    from tpuic.telemetry import spans
    records = spans.ledger.snapshot()
    sys.stdout.flush()
    print(format_tree(records, spans.self_time(records), t_launch),
          file=sys.stderr)
    if ns.json:
        with open(ns.json, "w") as f:
            json.dump({"t_launch": t_launch, "records": records}, f)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
