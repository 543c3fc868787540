"""Mode ``train``: whole ``Trainer.train_epoch`` calls for the window.

The system under test is the ``tpuic.train.loop.Trainer`` that
``train.config_from_args`` configures, on the flags of the configuration
and the traffic mix: the path of a user's ``python train.py``. The window
holds no eval pass and no checkpoint. The program's ``step`` events are
read from its event bus; the spans it lacks (epoch gaps) are taken here,
around the calls.
"""

from __future__ import annotations

import json
import math
import os
import time

from benchmark import harness
from benchmark.datagen import ensure_imagefolder
from benchmark.flops import model_forward_macs_per_image, train_step_flops

REFERENCE_IMAGES = 8


class StepLog:
    """The program's ``step`` events with their arrival time; in a traced
    run it also opens and closes the profiled slice, between two steps, in
    the training thread (bus subscribers run inline in the publisher)."""

    def __init__(self, ctx, trace_steps: int, trace_max_s: float,
                 sync=lambda: None) -> None:
        self.ctx = ctx
        self.sync = sync                # waits for every dispatched step
        self.events: list = []          # (perf_counter, event data)
        self.slice = harness.ProfiledSlice(ctx) if ctx.trace else None
        self.trace_steps, self.trace_max_s = trace_steps, trace_max_s
        self._slice_steps = 0
        self._step_span = None
        self.errors: list = []

    def __call__(self, ev) -> None:
        now = time.perf_counter()
        self.events.append((now, dict(ev.data)))
        try:
            self._drive_slice(now)
        except Exception as e:      # the bus swallows a subscriber's error
            self.errors.append(f"profiled slice: {type(e).__name__}: {e}")

    def _drive_slice(self, now: float) -> None:
        s = self.slice
        if (s is None or self.errors or self.ctx.t_open is None
                or s.t_end is not None):
            return
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None
        if not s.running:
            if now - self.ctx.t_open >= 0.4 * self.ctx.seconds:
                s.start()
        else:
            self._slice_steps += 1
            if (self._slice_steps >= self.trace_steps
                    or now - s.t_begin >= self.trace_max_s):
                # A step event marks a dispatch, and the host runs up to a
                # logging interval ahead of the device: wait for the steps
                # of the slice to have run before the trace closes.
                self.sync()
                s.stop()
                return
        if s.running:
            # One host span per step, from one step's end to the next:
            # the loader's __next__ and the dispatch of the step.
            self._step_span = harness.annotation("step")
            self._step_span.__enter__()

    def in_window(self, t0: float, t1: float, profiled: bool = False):
        return [d for t, d in self.events if t0 < t <= t1
                and (profiled or self.slice is None
                     or not self.slice.covers(t))]


def _flags(ctx, data_dir: str) -> list:
    work = ctx.work_dir
    return [*ctx.config["train_flags"], *ctx.traffic["train_flags"],
            "--datadir", data_dir, "--seed", str(ctx.seed),
            "--ckpt-dir", os.path.join(work, "ckpt"),
            "--log-dir", os.path.join(work, "log")]


def _logged_losses(ctx) -> list:
    path = os.path.join(ctx.work_dir, "log", "metrics.jsonl")
    try:
        with open(path) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
    except OSError:
        return []
    return [r["loss"] for r in rows if "loss" in r]


def _reference_check(ctx, trainer) -> list:
    """The Trainer's own model and variables, eval mode, against the plain
    float32 reference on seeded images. Outside the window."""
    import jax
    import numpy as np
    ref = harness.load_reference(ctx.config["reference"])
    size = int(ctx.config["image_size"])
    rng = np.random.default_rng(ctx.seed)
    images = rng.standard_normal(
        (REFERENCE_IMAGES, size, size, 3)).astype(np.float32)
    variables = harness.plain_variables(
        {"params": trainer.state.params,
         "batch_stats": trainer.state.batch_stats})
    if not variables["batch_stats"]:
        variables.pop("batch_stats")
    got = jax.jit(lambda v, x: trainer.model.apply(v, x, train=False))(
        variables, images)
    want = jax.jit(lambda v, x: ref.forward(v, x, ctx.config))(
        variables, images)
    err = harness.centred_error(got, want)
    tol = float(ctx.config["reference_tolerance"])
    ctx.say(f"reference: eval forward vs plain float32 on "
            f"{REFERENCE_IMAGES} images: centred relative error "
            f"{err:.5f} (tolerance {tol})")
    if not err <= tol:
        return [f"eval forward differs from the plain float32 reference "
                f"by {err:.5f} > {tol}"]
    return []


def run(ctx) -> harness.ModeResult:
    import jax
    mix, config = ctx.traffic, ctx.config
    data_dir = ensure_imagefolder(
        os.path.join(ctx.cache_dir, "data"), size=int(config["image_size"]),
        train_images=int(mix["train_images"]),
        val_images=int(mix["val_images"]), classes=int(mix["classes"]),
        unique_per_class=int(mix["unique_per_class"]),
        corpus_seed=int(mix["corpus_seed"]))
    ctx.say(f"data: {data_dir}")

    import train
    from tpuic.config import MeshConfig
    from tpuic.runtime.distributed import initialize
    from tpuic.runtime.mesh import make_mesh
    from tpuic.telemetry.events import subscribe
    from tpuic.train.loop import Trainer
    args = train.build_parser().parse_args(_flags(ctx, data_dir))
    cfg = train.config_from_args(args)
    where = ctx.enable_compile_cache()
    initialize()
    ctx.say(f"compile cache: {where}")
    mesh = make_mesh(MeshConfig(data=ctx.chips), devices=ctx.devices)
    trainer = Trainer(cfg, mesh=mesh, log_dir=args.log_dir)
    loader = trainer.train_loader
    steps_per_epoch = len(loader)
    global_batch = loader.global_batch
    ctx.say(f"trainer built: mesh={dict(trainer.mesh.shape)} global batch "
            f"{global_batch}, {steps_per_epoch} steps an epoch, loader "
            f"{'resident' if loader.resident else 'streaming'} "
            f"({loader.resident_bytes / 2**30:.2f} GiB on each chip)")
    problems = []
    if loader.resident != (mix["loader"] == "resident"):
        problems.append(f"the traffic asks for the {mix['loader']} loader "
                        "and the program chose the other")
    if global_batch != int(mix["per_chip_batch"]) * ctx.chips:
        problems.append(f"global batch {global_batch} is not "
                        f"{mix['per_chip_batch']} x {ctx.chips} chips")

    log = StepLog(ctx, int(mix["trace_steps"]), float(mix["trace_max_s"]),
                  sync=lambda: jax.block_until_ready(trainer.state))
    unsubscribe = subscribe(log, kinds=("step",))
    try:
        # Warm-up: one whole epoch. It compiles the step and the loader's
        # device programs, uploads the resident corpus and passes an epoch
        # boundary, so the window meets nothing for the first time.
        trainer.train_epoch(0)
        jax.block_until_ready(trainer.state)
        t_open = ctx.open_window()
        epoch = 1
        # At least two epochs (three when a slice is profiled), so that an
        # epoch boundary is always inside, and outside the slice.
        least = 3 if ctx.trace else 2
        while time.perf_counter() - t_open < ctx.seconds or epoch <= least:
            with harness.annotation("train_epoch"):
                trainer.train_epoch(epoch)
            epoch += 1
        jax.block_until_ready(trainer.state)
        t_close = ctx.close_window()
    finally:
        unsubscribe()
        if log.slice is not None and log.slice.running:
            log.slice.stop()
    elapsed = t_close - t_open
    steps = (epoch - 1) * steps_per_epoch
    images_per_s_per_chip = steps * global_batch / elapsed / ctx.chips
    ctx.say(f"window: {epoch - 1} epochs, {steps} steps, {elapsed:.3f} s, "
            f"{images_per_s_per_chip:.1f} images/s/chip")

    problems += log.errors
    counted = log.in_window(t_open, t_close, profiled=True)
    if len(counted) != steps:
        problems.append(f"{len(counted)} step events for {steps} steps")
    losses = _logged_losses(ctx)
    want0 = math.log(int(config["num_classes"]))
    if not losses or not all(math.isfinite(x) for x in losses):
        problems.append(f"logged losses are not all finite: {losses[:8]}")
    elif not 0.0 < losses[0] <= want0 + 1.0:
        # Random weights start at ln(classes). The first drain is 20 steps
        # in, and the corpus has 8 of the 1000 classes, so by then Adam has
        # legitimately pulled the loss below it (5.1-5.8 on the chip, PR
        # 23): only a loss above the start is a sign of a broken step.
        problems.append(f"first logged loss {losses[0]:.3f} is not in "
                        f"(0, ln(classes) + 1 = {want0 + 1:.3f}]")
    # Last step event of one epoch -> first step event of the next: the
    # tail of one train_epoch (final drain, bookkeeping), the head of the
    # next (device_get of the step counter, loader restart) and that first
    # step's own loader wait and dispatch.
    in_win = [(t, d["step"]) for t, d in log.events if t > t_open]
    gaps = [(t1 - t0) * 1e3 for (t0, s0), (t1, s1) in zip(in_win, in_win[1:])
            if s0 % steps_per_epoch == 0 and s1 == s0 + 1
            and not (log.slice is not None
                     and (log.slice.covers(t0) or log.slice.covers(t1)))]
    totals = sorted(d["total_ms"] for d in log.in_window(t_open, t_close))
    if totals:
        ctx.say(f"step events outside the slice: {len(totals)}, total_ms "
                f"median {totals[len(totals) // 2]:.2f} mean "
                f"{sum(totals) / len(totals):.2f} max {totals[-1]:.2f}; "
                f"epoch gaps ms {[round(g, 1) for g in gaps]}")
    problems += _reference_check(ctx, trainer)

    macs = model_forward_macs_per_image(trainer.model,
                                        int(config["image_size"]))
    spans = {**ctx.base_spans(),
             "epoch_gap_ms": gaps, "steps": steps,
             "steps_per_epoch": steps_per_epoch,
             "flops_per_step_per_chip":
                 train_step_flops(macs, global_batch) / ctx.chips,
             "device_kind": ctx.devices[0].device_kind}
    if spans["compiles_in_window"]:
        problems.append(f"{spans['compiles_in_window']} compile(s) inside "
                        "the window")
    trace = log.slice.reduce() if log.slice is not None else None
    return harness.ModeResult(
        end_to_end={"train_images_per_s_per_chip": images_per_s_per_chip},
        attempted=steps, failed=0, problems=problems,
        obs=harness.Observations(
            step_events=log.in_window(t_open, t_close), engine_stats={},
            trace=trace, spans=spans))
