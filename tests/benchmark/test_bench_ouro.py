"""The looped stack's plain reference against the program, at the tiny size
on the CPU in float32: eval logits, the objective, its gradient leaf by
leaf, three optimizer steps; what each planted fault reads; the new
cell's rehearsal with the step broken underneath; the reader of
``step_transient_gib``."""

import ast
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, train_check
from benchmark.reference import ouro, steps

SPEC = harness.load_spec()
CELL = next(w["name"] for w in SPEC["workloads"]
            if harness.resolve_cell(SPEC, w["name"])["config"]["reference"]
            == "ouro")
TINY = harness.resolve_cell(SPEC, CELL, tiny=True)
CONFIG, TRAFFIC = TINY["config"], TINY["traffic"]
MODEL = CONFIG["train_flags"][CONFIG["train_flags"].index("--model") + 1]


def _model(dtype="float32"):
    from tpuic.models import create_model
    return create_model(MODEL, CONFIG["num_classes"], dtype=dtype)


def _variables(seed=1):
    """Seeded weights with nothing left at its initial value (norm scales
    of 1 and a gate bias of 0 would hide how they enter)."""
    v = harness.plain_variables(_model().init(
        jax.random.key(seed), jnp.zeros((1, 32, 32, 3)), train=True))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.1 * rng.standard_normal(a.shape).astype(np.float32),
        v)


def _batches(n, rows=8, seed=0):
    rng = np.random.default_rng(seed)
    return [{"image": rng.standard_normal((rows, 32, 32, 3)).astype(
        np.float32), "label": rng.integers(0, CONFIG["num_classes"], rows
                                           ).astype(np.int32),
             "mask": np.ones(rows, np.float32)} for _ in range(n)]


def _program_loss(model, params, batch):
    from tpuic.train.loss import exit_expected_loss
    out = model.apply({"params": params}, batch["image"], train=True)
    return exit_expected_loss(
        out, batch["label"],
        entropy_weight=CONFIG["exit_entropy_weight"])[0]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_reference_imports_nothing_of_the_program():
    with open(ouro.__file__) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert names and not [n for n in names if n.split(".")[0] == "tpuic"]
    assert all(n.split(".")[0] in ("__future__", "jax", "numpy", "benchmark")
               for n in names)


def test_eval_logits_agree_and_are_the_last_pass():
    model, v = _model(), _variables()
    x = _batches(1)[0]["image"]
    want = ouro.forward(v, x, CONFIG)
    assert want.shape == (8, CONFIG["num_classes"])
    assert harness.centred_error(model.apply(v, x, train=False), want) < 1e-4
    # bfloat16 compute stays inside the rehearsal's tolerance
    assert harness.centred_error(
        _model("bfloat16").apply(v, x, train=False),
        want) < CONFIG["reference_tolerance"]
    logits, gates = ouro._passes(v, x, CONFIG, ouro.EVAL)
    assert logits.shape[0] == gates.shape[0] == CONFIG["total_ut_steps"] == 4
    np.testing.assert_array_equal(want, logits[-1])
    assert harness.centred_error(logits[0], want) > 0.05
    np.testing.assert_allclose(ouro.exit_probabilities(gates).sum(0), 1.0,
                               atol=1e-6)


def test_objective_and_gradient_agree_leaf_by_leaf():
    model, v = _model(), _variables()
    batch = _batches(1)[0]
    got, g_got = jax.value_and_grad(
        lambda p: _program_loss(model, p, batch))(v["params"])
    want, g_want = jax.value_and_grad(lambda p: ouro.train_loss(
        {"params": p}, batch["image"], batch["label"], CONFIG))(v["params"])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        g_got, g_want)
    flat = {jax.tree_util.keystr(k): e for k, e in
            jax.tree_util.tree_leaves_with_path(gaps)}
    assert len(flat) == 35 and max(flat.values()) < 1e-3, flat
    # no leaf is idle in the reference either: the gate, each block's four
    # norms and seven matrices, the closing norm
    assert all(float(jnp.linalg.norm(leaf)) > 1e-6
               for leaf in jax.tree_util.tree_leaves(g_want))
    # the entropy term is in it: another beta is another loss
    other = ouro.train_loss(v, batch["image"], batch["label"],
                            {**CONFIG, "exit_entropy_weight": 0.0})
    assert abs(float(other) - float(want)) > 1e-3


def _program_steps(batches, v, **changes):
    """Three steps of the program's own ``make_train_step`` in float32 on
    the traffic's optimizer, read as ``train_check`` reads a run."""
    import dataclasses
    import train
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import TrainState
    from tpuic.train.step import make_train_step
    args = train.build_parser().parse_args(
        [*CONFIG["train_flags"], *TRAFFIC["train_flags"], "--datadir", "x",
         "--dtype", "float32"])
    cfg = train.config_from_args(args)
    mcfg = dataclasses.replace(cfg.model, **changes)
    model = _model()
    tx = make_optimizer(cfg.optim, 8, 1, global_batch=8)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=v["params"],
                       batch_stats={}, opt_state=tx.init(v["params"]),
                       apply_fn=model.apply, tx=tx, ema_params=None,
                       skip_count=jnp.zeros((), jnp.int32))

    class Holder:
        train_step = staticmethod(make_train_step(cfg.optim, mcfg, mesh=None,
                                                  donate=False))
        state = None
    first = train_check.FirstSteps(Holder, len(batches))
    for batch in batches:
        state, _ = Holder.train_step(state, {k: jnp.asarray(b) for k, b in
                                             batch.items()})
    assert first.done
    return train_check.program_readings(first, TRAFFIC["optimizer"])


def test_three_steps_of_the_real_train_step_follow_the_reference():
    v, batches = _variables(), _batches(3)
    got = _program_steps(batches, v)
    want = steps.follow(ouro, v, batches, CONFIG, TRAFFIC["optimizer"])
    values, where = train_check.numbers(got, want)
    assert values["loss_gap"] < 1e-5, values
    assert values["grad_gap"] < 1e-3, (values, where)
    assert values["change_gap"] < 5e-3, (values, where)
    assert got["losses"][0] != got["losses"][1]
    limits = train_check.limits(CONFIG)
    assert all(values[name] < limit / 10 for name, limit in limits.items())


def test_one_pass_instead_of_four_fails_the_loss_and_the_forward():
    """The loop run once with the weights it has: what a ``while`` counted
    as one trip would compute. Planted in the reference put in the
    program's place, as ``readings.py`` plants its faults."""
    v, batches = _variables(), _batches(3)
    want = steps.follow(ouro, v, batches, CONFIG, TRAFFIC["optimizer"])
    once = {**CONFIG, "total_ut_steps": 1}
    values, _ = train_check.numbers(
        steps.follow(ouro, v, batches, once, TRAFFIC["optimizer"]), want)
    assert values["loss_gap"] > CONFIG["train_loss_tolerance"], values
    x = batches[0]["image"]
    assert harness.centred_error(ouro.forward(v, x, once), ouro.forward(
        v, x, CONFIG)) > CONFIG["reference_tolerance"]


def test_the_last_passes_loss_alone_fails_a_gradient_number():
    """The objective replaced by the plain cross-entropy of the last pass:
    the gate then has no gradient at all (its leaves read their own norm,
    over the median leaf's where that is larger) and the other leaves get
    the last pass's share alone."""
    from benchmark.reference.resnet import Mode, cross_entropy

    class LastPassOnly:
        forward = staticmethod(ouro.forward)

        @staticmethod
        def train_loss(variables, images, labels, config, mode=None):
            return cross_entropy(ouro.forward(
                variables, images, config, mode or Mode(train=True)), labels)
    v, batches = _variables(), _batches(3)
    want = steps.follow(ouro, v, batches, CONFIG, TRAFFIC["optimizer"])
    got = steps.follow(LastPassOnly, v, batches, CONFIG,
                       TRAFFIC["optimizer"])
    assert all(float(np.abs(g).max()) == 0.0 for g in
               jax.tree_util.tree_leaves(
                   got["gradient"]["backbone"]["exit_gate"]))
    values, where = train_check.numbers(got, want, full=True)
    gate = {k: g for k, g in where["grad_gaps"].items() if "exit_gate" in k}
    assert len(gate) == 2 and all(g > 0.05 for g in gate.values()), gate
    assert values["grad_gap"] > CONFIG["train_grad_tolerance"], values
    assert values["loss_gap"] > CONFIG["train_loss_tolerance"], values


# -- the rehearsal with the step broken underneath --------------------------

# the one-chip faults of the accepted cells' test, planted in this cell's step
from test_bench_train_faults import BROKEN  # noqa: E402

@pytest.mark.parametrize("fault", ["half_batch", "unchanged"])
def test_a_rehearsal_with_the_looped_step_broken_is_not_correct(fault,
                                                                tmp_path):
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, {repo!r})
        import tpuic.train.loop as loop
        real = loop.make_train_step
        def broken(*args, **kwargs):
            step = real(*args, **{{**kwargs, "donate": False}})
        {body}
            return wrapped
        loop.make_train_step = broken
        from benchmark import harness, run
        harness.CACHE_DIR = {cache!r}
        raise SystemExit(run.main(["--workload", {cell!r}, "--seed", "5",
                                   "--seconds", "1", "--trace", "0",
                                   "--tiny"]))
    """).format(repo=harness.REPO, cell=CELL, cache=str(tmp_path),
                body=textwrap.indent(textwrap.dedent(BROKEN[fault]), "    "))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run([sys.executable, "-c", code], cwd=harness.REPO,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    over = {k for k, c in line["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over - {"forward_gap"}, line["compared"]
    if fault == "unchanged":
        moved = {n for n in over if n.startswith("change_gap")}
        assert moved and all(0.9 < line["compared"][n]["value"] <= 1.0
                             for n in moved)


# -- the new reader ---------------------------------------------------------

def _obs(**spans):
    return harness.Observations(step_events=[], engine_stats={}, trace=None,
                                spans={"epoch_gap_ms": [], **spans})


def test_step_transient_is_the_peak_less_the_state_that_persists(
        ledger, fill_ledger):
    read = harness.load_reader("step_transient_gib")
    fill_ledger()
    # a program whose span lacks the attributes (the parent commit's)
    assert read(_obs(memory_peak_bytes=8 << 30)) is None
    init = next(r for r in ledger.snapshot()
                if r["name"] == "trainer.state_init")
    init["attrs"].update(param_bytes=1 << 30, opt_state_bytes=2 << 30)
    assert read(_obs(memory_peak_bytes=8 << 30)) == 5.0
    assert read(_obs()) is None                     # no peak: a CPU
    assert read(_obs(memory_peak_bytes=None)) is None
    assert read(harness.Observations(                # not a training run
        step_events=[], engine_stats={}, trace=None,
        spans={"memory_peak_bytes": 8 << 30})) is None
