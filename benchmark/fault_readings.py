#!/usr/bin/env python3
"""Faults of a model's own, read as ``benchmark/readings.py`` reads its:
on the chip, in one process, the reference with the fault planted put in
the program's place and compared with the sound reference on the batches
and the starting variables of a real run's first steps.

    python3 benchmark/fault_readings.py --workload <cell> --seeds 11,12,13
        [--set KEY=VALUE ...] [--plain-loss] [--out chiprun_out/readings]

``--set KEY=VALUE`` (JSON value) is one fault each: the reference computed
with that key of the configuration changed (a looped stack run once with
the weights it has: its key for the passes set to 1). The line holds the
numbers of ``benchmark/train_check.py`` and ``forward_gap`` of the changed
eval forward against the sound one, under ``what`` = ``KEY=VALUE``.

``--plain-loss`` is the fault "the objective replaced by the plain
cross-entropy of the eval forward's logits" (an expectation over exits or
an auxiliary term dropped), under ``what`` = ``plain_loss``.

Nothing here is part of a benchmark run; no window is measured.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s.strip()]


class PlainLoss:
    """A reference with its objective replaced by the plain cross-entropy
    of its own eval forward (hashable by identity: ``steps.follow`` keeps
    one compiled step for each reference)."""

    def __init__(self, ref) -> None:
        self.forward = ref.forward

    def train_loss(self, variables, images, labels, config, mode=None):
        from benchmark.reference.resnet import Mode, cross_entropy
        return cross_entropy(self.forward(
            variables, images, config,
            mode or Mode(train=True, remat=True)), labels)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--spec", default="BENCHMARK.json")
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    ap.add_argument("--plain-loss", action="store_true")
    ap.add_argument("--out", default="chiprun_out/readings")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from benchmark import harness, train_check
    from benchmark.modes import train as mode
    from benchmark.reference import steps
    resolved = harness.resolve_cell(harness.load_spec(args.spec),
                                    args.workload, tiny=args.tiny)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.tiny:
        print(f"fault_readings: {args.workload} needs a TPU, JAX found "
              f"{platform}", file=sys.stderr)
        return 3
    os.makedirs(os.path.join(REPO, args.out), exist_ok=True)
    out_path = os.path.join(REPO, args.out, args.workload + ".jsonl")

    def emit(row: dict) -> None:
        row = {"workload": args.workload, "platform": platform, **row}
        print(json.dumps(row), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")

    ref = harness.load_reference(resolved["config"]["reference"])
    plain = PlainLoss(ref)      # one object: one compiled step for all seeds
    for seed in args.seeds:
        ctx = harness.Context(resolved, seed=seed, seconds=0.0, trace=False,
                              tiny=args.tiny, t_start=T_START)
        try:
            trainer, _ = mode.build(ctx)
            first = train_check.FirstSteps(trainer, mode.CHECK_STEPS)
            trainer.train_epoch(0)
            jax.block_until_ready(trainer.state)
            optimizer = ctx.traffic["optimizer"]
            del trainer
            mode.free_device_arrays()
            size = int(ctx.config["image_size"])
            images = np.random.default_rng(seed).standard_normal(
                (mode.REFERENCE_IMAGES, size, size, 3)).astype(np.float32)

            def follow(module, config):
                return steps.follow(module, first.before, first.batches,
                                    config, optimizer)
            want = follow(ref, ctx.config)
            arms = [(setting, ref, {**ctx.config, setting.split("=", 1)[0]:
                                    json.loads(setting.split("=", 1)[1])})
                    for setting in args.set]
            if args.plain_loss:
                arms.append(("plain_loss", plain, ctx.config))
            for what, module, config in arms:
                values, where = train_check.numbers(follow(module, config),
                                                    want, full=True)
                if module is ref:       # the plain loss's forward is ref's
                    with jax.default_matmul_precision("highest"):
                        values["forward_gap"] = harness.centred_error(
                            ref.forward(first.before, images, config),
                            ref.forward(first.before, images, ctx.config))
                emit({"seed": seed, "what": what, **values, "where": where})
        finally:
            ctx.compiles.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
