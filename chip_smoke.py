#!/usr/bin/env python
"""The quickest proof that tpuic still starts on the chip.

    python chip_smoke.py            # one TPU chip: train, resume, serve, kernels
    python chip_smoke.py --chips 4  # four chips: data-parallel train vs one chip
                                    # (both: the resident loader's compiled gather)

Drives the program through the entry points a user calls (``train.py``,
``python -m tpuic.serve``) at published widths — ResNet-50 and ViT-B/16 at
224 px, 1000 classes — on a generated ImageFolder, with random weights made
from a fixed seed. The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
every other fact (per-phase seconds, compile seconds, step times, losses,
which input path is live) is on earlier lines. Any phase failing gives
``"ok": false`` and exit code 1. No work is done on a CPU: without a TPU
the script refuses before the first phase.

This parent is stdlib-only and never imports jax: a process that has
touched JAX holds the chip and a child that needs it then fails or hangs.
Each phase is one child, run to completion before the next starts, killed
only on its stated time limit.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
CLASSES = 1000
TOTAL_LIMIT_S = 1150          # the driver allows 1200 s, compiles included
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

_PROBE = """
import json, jax
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d)}))
"""

_DATA = """
import sys
from tpuic.data.synthetic import make_synthetic_imagefolder
root, seed = sys.argv[1], int(sys.argv[2])
classes = ("c0", "c1", "c2", "c3")
make_synthetic_imagefolder(root, classes, per_class=160, size=224,
                           folds=("train",), seed=seed)
make_synthetic_imagefolder(root, classes, per_class=16, size=224,
                           folds=("val",), seed=seed + 1)
"""

# One leg of the four-chip comparison: train.py's own parser and config,
# the Trainer on the mesh it builds from all devices ("mesh") or on a
# one-device mesh over the first chip ("one"). Prints where one batch and
# the parameters sit, then trains.
_LEG = """
import json, sys
import jax
import train
from tpuic.compiled.cache import enable_compile_cache
from tpuic.config import MeshConfig
from tpuic.runtime.distributed import initialize
from tpuic.runtime.mesh import make_mesh
from tpuic.train.loop import Trainer

leg = sys.argv[1]
args = train.build_parser().parse_args(sys.argv[2:])
cfg = train.config_from_args(args)
enable_compile_cache()
info = initialize()
mesh = None if leg == "mesh" else make_mesh(MeshConfig(data=1),
                                            devices=jax.devices()[:1])
trainer = Trainer(cfg, mesh=mesh, log_dir=args.log_dir)
it = trainer.train_loader.epoch(0)
image = next(it)["image"]
it.close()
leaves = jax.tree_util.tree_leaves(trainer.state.params)
print("[leg] " + json.dumps({
    "platform": info.platform, "kind": info.device_kind,
    "count": info.global_device_count,
    "mesh": {k: int(v) for k, v in trainer.mesh.shape.items()},
    "batch_shards": sorted((s.device.id, int(s.data.shape[0]))
                           for s in image.addressable_shards),
    "param_devices": sorted({d.id for x in leaves
                             for d in x.sharding.device_set}),
    "params_replicated": all(x.is_fully_replicated for x in leaves)}),
    flush=True)
trainer.fit()
"""


# The resident loader's per-batch program, compiled (nothing allocated) for
# a corpus of 4,096 images a chip at 224 px against a batch of 8 a chip:
# raises if its temporaries reach a quarter of the corpus, or if the
# augmentation reverses a float32 copy of the batch (the geometry belongs
# on the uint8 rows). On four chips, under the mesh.
_RESIDENT_PREP = """
import json
import jax
from tpuic.config import MeshConfig
from tpuic.data.device_prep import check_resident_prep
from tpuic.runtime.mesh import make_mesh

n = len(jax.devices())
mesh = make_mesh(MeshConfig(), jax.devices()) if n > 1 else None
facts = check_resident_prep(224, rows=4096 * n, batch=8 * n, mesh=mesh)
print("[resident_prep] " + json.dumps(dict(facts, devices=n)), flush=True)
"""


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def lines_with(text: str, *needles: str) -> list[str]:
    return [ln.strip() for ln in text.replace("\r", "\n").splitlines()
            if any(n in ln for n in needles)]


def read_jsonl(path: str) -> list[dict]:
    try:
        with open(path) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except (OSError, ValueError):
        return []


class Smoke:
    def __init__(self) -> None:
        self.t0 = time.monotonic()
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.data = os.path.join(self.work, "data")
        self.failed: list[str] = []
        self.device: dict = {}
        os.makedirs(LOG_DIR, exist_ok=True)

    def child(self, name: str, args: list[str], limit_s: float,
              stdin: str | None = None) -> tuple[int, str, str, float]:
        """Run ``python *args`` to completion (or its time limit); keep its
        output under chiprun_out/ and return (rc, stdout, stderr, secs)."""
        limit_s = min(limit_s, TOTAL_LIMIT_S - (time.monotonic() - self.t0))
        if limit_s <= 0:
            return 124, "", f"{name}: the run's total time limit is spent", 0.0
        env = dict(os.environ, PYTHONPATH=REPO, PYTHONUNBUFFERED="1",
                   TF_CPP_MIN_LOG_LEVEL="3")
        t = time.monotonic()
        try:
            p = subprocess.run([sys.executable, *args], cwd=REPO, env=env,
                               input=stdin, capture_output=True, text=True,
                               timeout=limit_s)
            rc, out, err = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            # The captured output comes back as bytes here, even in text mode.
            out, err = ((b or b"").decode(errors="replace")
                        for b in (e.stdout, e.stderr))
            rc, err = 124, err + f"\n{name}: killed at its {limit_s:.0f} s limit"
        for ext, body in (("out", out), ("err", err)):
            with open(os.path.join(LOG_DIR, f"{name}.{ext}"), "w") as f:
                f.write(body)
        return rc, out, err, time.monotonic() - t

    def train_child(self, name: str, flags: tuple[str, ...], steps: range,
                    limit_s: float, ckpt: str = "",
                    launcher: tuple[str, ...] = ("train.py",),
                    near_log_c: bool = True) -> dict:
        """One trainer child at 224 px / 1000 classes on the generated data,
        logging every step. Returns its output, the problems found so far
        (exit code, a finite loss at each of ``steps``), the losses, and
        set-up / compile / per-step times from its telemetry JSONL."""
        log = os.path.join(self.work, f"log_{name}")
        events = os.path.join(LOG_DIR, f"{name}.events.jsonl")
        if os.path.exists(events):
            os.remove(events)           # the sink appends
        started = time.time()
        rc, out, err, secs = self.child(name, [
            *launcher, "--datadir", self.data, "--num-classes", str(CLASSES),
            "--resize", "224", "--no-class-weights", "--milestones",
            "--ckpt-dir", ckpt or os.path.join(self.work, f"ck_{name}"),
            "--save-period", "1", "--log-every-steps", "1", "--log-dir", log,
            "--metrics-jsonl", events, "--seed", str(SEED), *flags], limit_s)
        problems = [f"exit code {rc}"] if rc else []
        by_step = {int(r["step"]): float(r["loss"]) for r in read_jsonl(
            os.path.join(log, "metrics.jsonl")) if "loss" in r}
        losses = [by_step.get(i) for i in steps]
        if any(x is None or not math.isfinite(x) for x in losses):
            problems.append(f"want a finite loss at each of steps "
                            f"{steps.start}..{steps.stop - 1}, got {losses}")
        elif near_log_c and abs(losses[0] - math.log(CLASSES)) > 1.0:
            # A classifier with random weights starts near ln(classes).
            problems.append(f"first loss {losses[0]:.3f} is not near "
                            f"ln({CLASSES}) = {math.log(CLASSES):.3f}")
        ev = read_jsonl(events)
        final = [r for r in ev if r.get("event") == "goodput"
                 and r.get("final")]
        step_ev = [r for r in ev if r.get("event") == "step"]
        times = {  # set-up: process start to the first step's start
            "setup_s": round(step_ev[0]["t"] - step_ev[0]["total_ms"] / 1e3
                             - started, 1) if step_ev else None,
            "compile_s": final[-1]["compile_s"] if final else None,
            "step_ms": [round(r["total_ms"], 1) for r in step_ev]}
        return {"out": out, "err": err, "secs": secs, "problems": problems,
                "losses": losses, "times": times}

    def phase(self, name: str, secs: float, problems: list[str],
              err: str = "", **facts) -> bool:
        ok = not problems
        say(f"phase={name} ok={ok} seconds={secs:.1f} "
            + " ".join(f"{k}={v}" for k, v in facts.items()))
        if not ok:
            self.failed.append(name)
            for p in problems:
                say(f"  {name}: {p}")
            for line in lines_with(err, "")[-25:]:
                say(f"  {name} stderr| {line[:300]}")
        return ok

    def train_phase(self, name: str, r: dict, **facts) -> bool:
        return self.phase(name, r["secs"], r["problems"], r["err"],
                          losses=r["losses"], **facts, **r["times"])

    def finish(self) -> int:
        shutil.rmtree(self.work, ignore_errors=True)
        ok = not self.failed and self.device.get("platform") == "tpu"
        say(f"total seconds={time.monotonic() - self.t0:.1f} "
            f"failed={self.failed or 'none'}")
        print(json.dumps({"ok": ok, "device": self.device}), flush=True)
        return 0 if ok else 1


def check_device_line(text: str, tag: str, want: dict,
                      problems: list[str]) -> None:
    """The child's own start-up line must name the probed device."""
    m = re.search(rf"\[{tag}\] (?:\d+ process\(es\), )?(\d+) (\w+) "
                  r"device\(s\), device_kind='([^']*)'", text)
    got = ({"platform": m.group(2), "kind": m.group(3),
            "count": int(m.group(1))} if m else None)
    if got != want:
        problems.append(f"[{tag}] start-up line reports {got}, "
                        f"the probe saw {want}")


def max_diff(a: list[float], b: list[float]) -> float:
    return max(abs(x - y) for x, y in zip(a, b))


RESNET = ("--model", "resnet50", "--optimizer", "sgd")


def train_and_resume(s: Smoke, ck: str) -> bool:
    # -- train: 640 images / batch 128 = an epoch of five steps, then the
    # eval pass and a checkpoint commit, then two steps of epoch 1. (--steps
    # stops without the eval pass, so --steps 5 alone would commit nothing:
    # the epoch is sized to five steps and the budget cuts the second one.)
    flags = (*RESNET, "--batchsize", "128", "--epochs", "2", "--steps", "7")
    cold = s.train_child("train", flags, range(1, 8), 500, ckpt=ck)
    check_device_line(cold["out"], "tpuic", s.device, cold["problems"])
    if not os.path.isfile(os.path.join(ck, "resnet50",
                                       "latest.manifest.json")):
        cold["problems"].append("no committed checkpoint "
                                "(latest.manifest.json)")
    if not lines_with(cold["out"], "Val Accuracy"):
        cold["problems"].append("no eval pass in the output")
    for ln in lines_with(cold["out"], "[tpuic] ", "[pack]", "[model]",
                         "[ckpt]", "Val Accuracy", "[goodput]"):
        say(f"  train| {ln[:400]}")
    if not s.train_phase("train", cold):
        return False

    # -- resume: the same command again restores the epoch-0 checkpoint and
    # trains steps 6 and 7 once more. The persistent compile cache holds
    # the first run's programs now, so the two compile_s are worth comparing
    # (on a machine whose cache was warm already they come out alike).
    warm = s.train_child("resume", flags, range(6, 8), 400, ckpt=ck,
                         near_log_c=False)
    if not re.search(r"\[ckpt\] restored .*\(epoch 0,", warm["out"]):
        warm["problems"].append("no '[ckpt] restored ... (epoch 0, ...)' "
                                "line")
    if not warm["problems"] and max_diff(warm["losses"],
                                         cold["losses"][5:]) > 0.02:
        # Same state, same batches: the restored run repeats steps 6-7.
        warm["problems"].append(f"the first run's steps 6-7 gave "
                                f"{cold['losses'][5:]}")
    for ln in lines_with(warm["out"], "[ckpt]", "[goodput]"):
        say(f"  resume| {ln[:400]}")
    s.train_phase("resume", warm,
                  compile_s_first_run=cold["times"]["compile_s"])
    return True


def serve(s: Smoke, ck: str) -> None:
    """PNG paths in, one prediction line per request out."""
    paths = sorted(os.path.join(d, f) for d, _, fs in
                   os.walk(os.path.join(s.data, "val")) for f in fs)[::8][:8]
    reqs = [{"id": f"r{i}", "path": p,
             **({"serve_dtype": "bf16"} if i % 2 else {})}
            for i, p in enumerate(paths)]
    rc, out, err, secs = s.child("serve", [
        "-m", "tpuic.serve", "--ckpt-dir", ck, "--model", "auto", "--track",
        "latest", "--buckets", "1,8,32", "--serve-dtypes", "bf16"], 500,
        stdin="".join(json.dumps(r) + "\n" for r in reqs))
    problems = [f"exit code {rc}"] if rc else []
    check_device_line(err, "serve", s.device, problems)
    answers: dict[str, list[dict]] = {}
    for ln in out.splitlines():
        try:
            r = json.loads(ln)
        except ValueError:
            continue
        if isinstance(r, dict) and "id" in r:
            answers.setdefault(r["id"], []).append(r)
    for q in reqs:
        a = answers.get(q["id"], [])
        if len(a) != 1 or "pred" not in a[0] or not (
                isinstance(a[0].get("prob"), float)
                and 0.0 < a[0]["prob"] <= 1.0):
            problems.append(f"request {q['id']}: want one {{id, pred, prob}} "
                            f"line, got {a}")
    # The engine counts every compile; all of them must be the warm-up's.
    m = re.search(r"\[serve\] served \d+ requests; stats: (\{.*\})", err)
    stats = json.loads(m.group(1)) if m else {}
    warm_n = re.search(r"warmup compiled (\d+) bucket executables", err)
    steady = (stats["compiles"] - int(warm_n.group(1))
              if stats and warm_n else None)
    if steady != 0:
        problems.append(f"steady-state compiles = {steady} (engine counter "
                        "minus warm-up), want 0")
    for ln in lines_with(err, "device(s)", "accuracy gate", "warmup compiled",
                         "[ckpt]"):
        say(f"  serve| {ln[:400]}")
    s.phase("serve", secs, problems, err, answered=len(answers),
            steady_compiles=steady, warmup_compile_s=stats.get("compile_s"),
            latency_ms=stats.get("latency_ms"),
            preds=[a[0].get("pred") for a in answers.values()])


def kernels(s: Smoke) -> None:
    """Flash attention fwd/bwd, the fused CE and the fused LARS update each
    execute on the chip inside three ViT-B/16 steps; the same three steps
    through the plain paths are the reference."""
    vit = ("--model", "vit-b16", "--batchsize", "64", "--optimizer", "lars",
           "--lr", "1.0", "--steps", "3", "--no-resume")
    fused = s.train_child("kernels", (
        *vit, "--attention", "flash", "--fused-loss", "--fused-optimizer"),
        range(1, 4), 400)
    for ln in lines_with(fused["out"], "pallas kernels"):
        say(f"  kernels| {ln[:400]}")
    if "interpret=False, fused optimizer impl='pallas'" not in fused["out"]:
        fused["problems"].append("kernels are not live: want interpret=False "
                                 "and impl 'pallas' in the [tpuic] line")
    fused_ok = s.train_phase("kernels", fused)
    plain = s.train_child("kernels_ref", vit, range(1, 4), 400)
    if fused_ok and not plain["problems"]:
        # Same seed, data and steps: the two differ only by bf16 rounding
        # inside the attention and the loss.
        diff = max_diff(plain["losses"], fused["losses"])
        if diff > 0.05:
            plain["problems"].append(f"losses differ from the fused run's "
                                     f"{fused['losses']} by {diff:.4f} "
                                     "(> 0.05)")
    s.train_phase("kernels_ref", plain)


def resident_prep(s: Smoke) -> None:
    """A batch of the resident loader reads its rows of the corpus in
    place: the compiled program holds no corpus-sized temporary."""
    rc, out, err, secs = s.child("resident_prep", ["-c", _RESIDENT_PREP], 180)
    m = re.search(r"^\[resident_prep\] (\{.*\})$", out, re.M)
    problems = [f"exit code {rc}"] if rc else []
    if not m:
        problems.append("no [resident_prep] line on stdout")
    s.phase("resident_prep", secs, problems, err,
            **(json.loads(m.group(1)) if m else {}))


def four_chips(s: Smoke) -> None:
    """Data parallelism over a data=4 mesh against the same global batch
    on one of the four chips: same seed, same data, five steps."""
    runs = {}
    for leg, n in (("mesh", 4), ("one", 1)):
        r = s.train_child(f"leg_{leg}", (
            *RESNET, "--batchsize", str(128 // n), "--steps", "5",
            "--no-resume"), range(1, 6), 500, launcher=("-c", _LEG, leg))
        m = re.search(r"^\[leg\] (\{.*\})$", r["out"], re.M)
        where = json.loads(m.group(1)) if m else {}
        shards = where.get("batch_shards", [])
        if (len({d for d, _ in shards}) != n
                or [rows for _, rows in shards] != [128 // n] * n):
            r["problems"].append(f"batch shards {shards}: want {128 // n} "
                                 f"rows on each of {n} distinct device(s)")
        if (len(where.get("param_devices", [])) != n
                or not where.get("params_replicated")):
            r["problems"].append(
                f"parameters on {where.get('param_devices')}, replicated="
                f"{where.get('params_replicated')}: want replicated on {n} "
                "device(s)")
        if leg == "one" and not r["problems"] and "mesh" in runs:
            # The jitted step normalises over the global batch of 128 in
            # both layouts; only the reduction order differs.
            diff = max_diff(r["losses"], runs["mesh"])
            if diff > 0.02:
                r["problems"].append(f"losses differ from the four-chip "
                                     f"leg by {diff:.4f} (> 0.02, bf16)")
        if s.train_phase(f"leg_{leg}", r, layout=json.dumps(where)):
            runs[leg] = r["losses"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the data-parallel comparison")
    args = ap.parse_args()
    s = Smoke()

    # First act: learn the device from one short child that exits on its
    # own, and refuse anything but the TPU count asked for.
    rc, out, err, secs = s.child("probe", ["-c", _PROBE], 120)
    try:
        s.device = json.loads(out.strip().splitlines()[-1])
    except (ValueError, IndexError):
        s.device = {}
    problems = [f"exit code {rc}"] if rc else []
    if s.device.get("platform") != "tpu":
        problems.append(f"JAX found no TPU (device: {s.device or None}); "
                        "no phase runs on a CPU")
    elif s.device.get("count") != args.chips:
        problems.append(f"{s.device.get('count')} chip(s) here, --chips "
                        f"{args.chips} asked for")
    if not os.path.isfile(os.path.join(REPO, "train.py")):
        problems.append("train.py is not beside chip_smoke.py")
    if not s.phase("probe", secs, problems, err, device=s.device):
        return s.finish()

    rc, out, err, secs = s.child("data", ["-c", _DATA, s.data, str(SEED)],
                                 240)
    if s.phase("data", secs, [f"exit code {rc}"] if rc else [], err,
               train_images=640, val_images=64, px=224):
        resident_prep(s)
        if args.chips == 4:
            four_chips(s)
        else:
            ck = os.path.join(s.work, "ck")
            if train_and_resume(s, ck):
                serve(s, ck)
            kernels(s)
    return s.finish()


if __name__ == "__main__":
    raise SystemExit(main())
