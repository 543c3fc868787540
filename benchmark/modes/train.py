"""Mode ``train``: whole ``Trainer.train_epoch`` calls for the window.

The system under test is the ``tpuic.train.loop.Trainer`` that
``train.config_from_args`` configures, on the flags of the configuration
and the traffic mix: the path of a user's ``python train.py``. The window
holds no eval pass and no checkpoint. The program's ``step`` events are
read from its event bus; the spans it lacks (epoch gaps) are taken here,
around the calls.

``correct`` is decided by ``benchmark/train_check.py``: the first steps of
the warm-up epoch, which go through the compiled step, the call and the
feed that the window then drives, are kept on the host and followed by the
plain reference once the window has closed and the program's arrays are
freed. The eval forward of the trained variables is compared as well.
"""

from __future__ import annotations

import json
import math
import os
import time

from benchmark import harness, train_check
from benchmark.datagen import ensure_imagefolder
from benchmark.flops import model_forward_macs_per_image, train_step_flops

REFERENCE_IMAGES = 8
CHECK_STEPS = 3


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


def _forward_check(ctx, ref, model, variables):
    """The program's model on its trained variables, eval mode, against the
    plain float32 reference's forward on seeded images: ``(centred relative
    error, limit)``."""
    import jax
    import numpy as np
    size = int(ctx.config["image_size"])
    rng = np.random.default_rng(ctx.seed)
    images = rng.standard_normal(
        (REFERENCE_IMAGES, size, size, 3)).astype(np.float32)
    got = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, images)
    want = jax.jit(lambda v, x: ref.forward(v, x, ctx.config))(
        variables, images)
    return (harness.centred_error(got, want),
            float(ctx.config["reference_tolerance"]))


def free_device_arrays(least_bytes: int = 1 << 20) -> int:
    """Delete every array of a MiB or more that the process holds on its
    devices (the program's state, its resident corpus, the loader's batches
    in flight) so that the reference has the chip's memory to itself;
    returns the bytes device 0 still holds. The peak has been read by now:
    it never falls again."""
    import gc
    import jax
    for a in jax.live_arrays():
        if a.nbytes >= least_bytes:
            a.delete()
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))


def batch_placer(ctx):
    """Places a host batch as the program's loader does: rows over the
    mesh's chips, or on the one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    if ctx.chips == 1:
        return None
    mesh = Mesh(np.array(ctx.devices), ("data",))
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    return lambda a: jax.device_put(a, sharding)


def reference_check(ctx, model, variables, first):
    """``(problems, compared)`` once the window has closed and the peak
    memory has been read: the program's first steps (``first``, a
    ``train_check.FirstSteps``) against the reference's, and its eval
    forward on the trained ``variables`` (a host copy). ``compared`` maps
    each number's name to ``{"value", "limit"}``."""
    from benchmark.reference import steps
    problems, compared = [], {}
    ref = harness.load_reference(ctx.config["reference"])
    held = free_device_arrays()
    err, tol = _forward_check(ctx, ref, model, variables)
    compared["forward_gap"] = {"value": err, "limit": tol}
    if not callable(getattr(ref, "train_loss", None)):
        return ([f"the reference {ctx.config['reference']!r} has no "
                 "train_loss: the timed step is compared with nothing"],
                compared)
    if not first.done:
        return ([f"the warm-up epoch made {len(first.batches)} of the "
                 f"{first.steps} steps that are compared"], compared)
    if any((b["mask"] != 1).any() for b in first.batches):
        problems.append("a compared batch holds padded rows: the reference's "
                        "loss is the plain mean")
    optimizer = ctx.traffic["optimizer"]
    got = train_check.program_readings(first, optimizer)
    t0 = time.perf_counter()
    events = len(ctx.compiles.events)
    try:
        want = steps.follow(ref, first.before, first.batches, ctx.config,
                            optimizer, put=batch_placer(ctx))
    except steps.StepDoesNotFit as e:
        problems.append(str(e))
        return problems, compared
    values, where = train_check.numbers(got, want)
    memory = want["memory"]
    ctx.say(f"reference: {first.steps} steps followed in "
            f"{time.perf_counter() - t0:.2f} s ("
            f"{len(ctx.compiles.events) - events} compiles or cache loads), "
            f"{held / 2**20:.0f} MiB still held by the program; step "
            f"{memory['step']} bytes compiled ("
            f"{memory['step'] / memory['parameters']:.2f} B a parameter "
            f"over {memory['parameters']}; temporaries {memory['temp']}); "
            f"losses program {got['losses']} reference {want['losses']}; "
            f"numbers {values}; worst leaves {where}")
    limits = train_check.limits(ctx.config)
    if "loss_gap" not in limits or len(limits) < 3:
        problems.append("the configuration holds limits for "
                        f"{sorted(limits)}: the loss and a number each of "
                        "the gradient and of the change have to be compared")
    for name, limit in limits.items():
        compared[name] = {"value": values[name], "limit": limit}
    for name, c in compared.items():
        if not c["value"] <= c["limit"]:
            problems.append(f"{name} {c['value']:.6g} is over its limit "
                            f"{c['limit']:.6g}")
    return problems, compared


def build(ctx):
    """The Trainer of this cell on ``ctx``'s seed, and what is already
    wrong with it: ``(trainer, problems)``."""
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
    from tpuic.train.loop import Trainer
    args = train.build_parser().parse_args(_flags(ctx, data_dir))
    cfg = train.config_from_args(args)
    where = ctx.enable_compile_cache()
    initialize()
    ctx.say(f"compile cache: {where}")
    mesh = make_mesh(MeshConfig(data=ctx.chips), devices=ctx.devices)
    trainer = Trainer(cfg, mesh=mesh, log_dir=args.log_dir)
    loader = trainer.train_loader
    ctx.say(f"trainer built: mesh={dict(trainer.mesh.shape)} global batch "
            f"{loader.global_batch}, {len(loader)} steps an epoch, loader "
            f"{'resident' if loader.resident else 'streaming'} "
            f"({loader.resident_bytes / 2**30:.2f} GiB on each chip)")
    problems = []
    if loader.resident != (mix["loader"] == "resident"):
        problems.append(f"the traffic asks for the {mix['loader']} loader "
                        "and the program chose the other")
    if loader.global_batch != int(mix["per_chip_batch"]) * ctx.chips:
        problems.append(f"global batch {loader.global_batch} is not "
                        f"{mix['per_chip_batch']} x {ctx.chips} chips")
    return trainer, problems


def run(ctx) -> harness.ModeResult:
    import jax
    from tpuic.telemetry.events import subscribe
    mix, config = ctx.traffic, ctx.config
    trainer, problems = build(ctx)
    steps_per_epoch = len(trainer.train_loader)
    global_batch = trainer.train_loader.global_batch
    first = train_check.FirstSteps(trainer, CHECK_STEPS)

    log = StepLog(ctx, int(mix["trace_steps"]), float(mix["trace_max_s"]),
                  sync=lambda: jax.block_until_ready(trainer.state))
    unsubscribe = subscribe(log, kinds=("step",))
    try:
        # Warm-up: one whole epoch. It compiles the step and the loader's
        # device programs, uploads the resident corpus and passes an epoch
        # boundary, so the window meets nothing for the first time. Its
        # first steps are the ones compared (``first`` keeps them and then
        # hands the step back).
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
    # The peak memory is read (base_spans): now the reference may have the
    # chip.
    wrong, compared = reference_check(
        ctx, trainer.model, train_check.host_variables(trainer.state), first)
    return harness.ModeResult(
        end_to_end={"train_images_per_s_per_chip": images_per_s_per_chip},
        attempted=steps, failed=0, problems=problems + wrong,
        compared=compared,
        obs=harness.Observations(
            step_events=log.in_window(t_open, t_close), engine_stats={},
            trace=trace, spans=spans))
