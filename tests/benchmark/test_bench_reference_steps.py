"""The reference's training step and what it holds: ``steps.follow`` at
the tiny size of every configuration, on the CPU, against a frozen copy of
the ``follow`` that held the starting parameters on the device and donated
nothing. Donating the state and keeping the start on the host moves no bit
of what the check compares; the step writes its update in place; the
caller's arrays survive; an executable loaded from the persistent cache
reads what a fresh one reads; and a step that cannot fit the device is a
problem line of the check, not an allocation failure."""

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, train_check
from benchmark.modes import train as mode
from benchmark.reference import steps
from benchmark.reference.resnet import Mode

SPEC = harness.load_spec()
CONFIGS = [c["name"] for c in SPEC["configs"]]
ROUTED = ("kanana2_30b_a3b", "mellum2_12b_a2.5b", "keye_vl2_30b_a3b")
OPTIMIZERS = {
    "adam": {"name": "adam", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
             "eps": 1e-8},
    "sgd": {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9},
}
CASES = [(c, o) for c in CONFIGS for o in OPTIMIZERS]


# -- the follow this change replaced, frozen ---------------------------------

@functools.lru_cache(maxsize=None)
def _frozen_step(ref, config_json, optimizer_json, rounding, stat_groups):
    config, optimizer = json.loads(config_json), json.loads(optimizer_json)
    mode_ = Mode(train=True, rounding=rounding, stat_groups=stat_groups,
                 remat=True)

    def loss_of(params, rest, images, labels):
        return ref.train_loss({"params": params, **rest}, images, labels,
                              config, mode_)

    @jax.jit
    def step(params, rest, opt, images, labels):
        loss, grads = jax.value_and_grad(loss_of)(params, rest, images,
                                                  labels)
        new, opt = steps.optimizer_update(optimizer, params, grads, opt)
        return new, opt, loss, grads
    return step


def frozen_follow(ref, variables, batches, config, optimizer, *,
                  rounding=None, stat_groups=1, rows=None, put=None):
    step = _frozen_step(ref, json.dumps(config, sort_keys=True),
                        json.dumps(optimizer, sort_keys=True), rounding,
                        int(stat_groups))
    rest = {k: v for k, v in variables.items() if k != "params"}
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])
    start, opt = params, steps.optimizer_init(optimizer, params)
    losses, gradient = [], None
    for batch in batches:
        images, labels = batch["image"], batch["label"]
        if rows is not None:
            images, labels = images[rows], labels[rows]
        if put is not None:
            images, labels = put(images), put(labels)
        with jax.default_matmul_precision("highest"):
            params, opt, loss, grads = step(params, rest, opt, images,
                                            labels)
        losses.append(float(loss))
        if gradient is None:
            gradient = jax.device_get(grads)
        del grads
    change = jax.device_get(jax.tree_util.tree_map(
        lambda a, b: a - b, params, start))
    return {"losses": losses, "gradient": gradient, "change": change}


# -- the tiny cases ----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _case(name):
    """``(reference, config, variables, batches)`` of the configuration's
    ``tiny`` block: the program's model initialised from a seed, every
    variable moved off its initial value, and three batches."""
    from tpuic.models import create_model
    cell = next(w["name"] for w in SPEC["workloads"] if w["config"] == name)
    config = harness.resolve_cell(SPEC, cell, tiny=True)["config"]
    flags = config["train_flags"]
    size, classes = int(config["image_size"]), int(config["num_classes"])
    model = create_model(flags[flags.index("--model") + 1], classes,
                         dtype="float32")
    v = harness.plain_variables(model.init(
        jax.random.key(1), jnp.zeros((1, size, size, 3)), train=False))
    rng = np.random.default_rng(1)
    v = jax.tree_util.tree_map(
        lambda a: a + 0.05 * np.abs(rng.standard_normal(a.shape)
                                    ).astype(np.float32), v)
    rows = 2 if size > 64 else 4
    batches = [{"image": rng.standard_normal((rows, size, size, 3)).astype(
        np.float32), "label": rng.integers(0, classes, rows).astype(np.int32),
        "mask": np.ones(rows, np.float32)} for _ in range(3)]
    return harness.load_reference(config["reference"]), config, v, batches


@functools.lru_cache(maxsize=None)
def _runs(name, optimizer):
    ref, config, v, batches = _case(name)
    return (steps.follow(ref, v, batches, config, OPTIMIZERS[optimizer]),
            frozen_follow(ref, v, batches, config, OPTIMIZERS[optimizer]))


def _same(got, want):
    assert got["losses"] == want["losses"]
    for key in ("gradient", "change"):
        a, b = (jax.tree_util.tree_leaves_with_path(x[key])
                for x in (got, want))
        assert [p for p, _ in a] == [p for p, _ in b]
        for (path, x), (_, y) in zip(a, b):
            assert np.asarray(x).dtype == np.asarray(y).dtype, (key, path)
            assert np.array_equal(np.asarray(x), np.asarray(y)), (key, path)


def _bytes(tree):
    return sum(np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("name,optimizer", CASES)
def test_follow_reads_bit_for_bit_what_it_read_before(name, optimizer):
    got, want = _runs(name, optimizer)
    assert len(got["losses"]) == 3 and all(np.isfinite(got["losses"]))
    _same(got, want)


@pytest.mark.parametrize("name,optimizer", CASES)
def test_the_step_writes_parameters_and_state_in_place(name, optimizer):
    """The donation took: the compiled step's aliased bytes cover the
    parameters and the optimizer's whole state, and what it holds beyond
    its temporaries, one batch and a table of its outputs (8 bytes a
    leaf) is 16 bytes a parameter under adam (12 under sgd)."""
    got, _ = _runs(name, optimizer)
    _, config, v, batches = _case(name)
    opt = steps.optimizer_init(OPTIMIZERS[optimizer], v["params"])
    memory = got["memory"]
    assert memory["alias"] >= _bytes(v["params"]) + _bytes(opt)
    assert memory["parameters"] == sum(
        np.size(a) for a in jax.tree_util.tree_leaves(v["params"]))
    held = memory["step"] - memory["temp"]
    per = {"adam": 16, "sgd": 12}[optimizer]
    leaves = len(jax.tree_util.tree_leaves(v["params"]))
    small = _bytes({k: x for k, x in v.items() if k != "params"}) + _bytes(
        [batches[0]["image"], batches[0]["label"]]) + 8 * (4 * leaves + 8)
    assert per * memory["parameters"] <= held <= (
        per * memory["parameters"] + small)


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_follow_over_a_mesh_reads_bit_for_bit_what_it_read_before(
        optimizer):
    """As the data=4 cell's check runs it: each batch's rows placed over
    four devices by ``batch_placer``, the donated parameters and state
    on one device at the first step and where the step put them after."""
    ref, config, v, batches = _case("resnet50")
    ctx = types.SimpleNamespace(chips=4, devices=jax.devices()[:4])
    put = mode.batch_placer(ctx)
    got = steps.follow(ref, v, batches, config, OPTIMIZERS[optimizer],
                       put=put)
    want = frozen_follow(ref, v, batches, config, OPTIMIZERS[optimizer],
                         put=put)
    _same(got, want)
    assert got["memory"]["alias"] >= _bytes(v["params"])


@pytest.mark.parametrize("name", CONFIGS)
def test_follow_twice_on_device_arrays_leaves_them_and_reads_alike(
        name, monkeypatch):
    """The step runs on buffers of its own. (A CPU does not donate a buffer
    that a host view shares, and ``follow`` takes such a view of device
    arrays, so the buffers the step is given are compared as well: where
    they are the caller's, a chip would delete them.)"""
    ref, config, v, batches = _case(name)
    on_device = jax.tree_util.tree_map(jnp.asarray, v)
    leaves = jax.tree_util.tree_leaves(on_device)
    theirs = {a.unsafe_buffer_pointer() for a in leaves}
    given = []
    init = steps.optimizer_init

    def spy(spec, params):
        given.extend(a.unsafe_buffer_pointer()
                     for a in jax.tree_util.tree_leaves(params)
                     if not isinstance(a, jax.core.Tracer))
        return init(spec, params)
    monkeypatch.setattr(steps, "optimizer_init", spy)
    first = steps.follow(ref, on_device, batches, config, OPTIMIZERS["adam"])
    second = steps.follow(ref, on_device, batches, config,
                          OPTIMIZERS["adam"])
    assert len(given) == 2 * len(jax.tree_util.tree_leaves(v["params"]))
    assert not theirs & set(given)
    assert not any(a.is_deleted() for a in leaves)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(v)):
        assert np.array_equal(np.asarray(a), b)
    _same(first, second)
    _same(first, _runs(name, "adam")[0])


@pytest.mark.parametrize("name,optimizer",
                         [(c, o) for c, o in CASES if c in ROUTED])
def test_a_step_loaded_from_the_persistent_cache_reads_alike(
        name, optimizer, tmp_path):
    """As the harness runs it: the persistent cache on, every program kept
    (the minimum compile time at 0). The first run compiles and writes the
    donating step; with the process's caches cleared the second loads it
    from the directory. Both read what the frozen follow reads."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    ref, config, v, batches = _case(name)
    _, want = _runs(name, optimizer)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    hits = []

    def on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)
    jax.monitoring.register_event_listener(on_event)
    try:
        cc.reset_cache()
        cc.set_cache_dir(str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        got = []
        for _ in range(2):
            jax.clear_caches()
            steps._step.cache_clear()
            steps._COMPILED.clear()
            hits.clear()
            got.append(steps.follow(ref, v, batches, config,
                                    OPTIMIZERS[optimizer]))
        assert hits, "the second run compiled again"
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
    _same(got[0], want)
    _same(got[1], want)


def test_a_step_over_the_devices_limit_is_refused_before_it_runs(
        monkeypatch):
    ref, config, v, batches = _case("vit_b16")
    need = _runs("vit_b16", "adam")[0]["memory"]["step"]
    monkeypatch.setattr(steps, "bytes_limit", lambda: need)
    steps.follow(ref, v, batches, config, OPTIMIZERS["adam"])    # it fits
    monkeypatch.setattr(steps, "bytes_limit", lambda: need - 1)
    with pytest.raises(steps.StepDoesNotFit) as e:
        steps.follow(ref, v, batches, config, OPTIMIZERS["adam"])
    assert f"needs {need} bytes" in str(e.value)
    assert f"bytes_limit is {need - 1}" in str(e.value)
    assert " B a parameter" in str(e.value)


def test_reference_check_turns_a_step_that_cannot_fit_into_a_problem(
        monkeypatch):
    """The check on a run's first steps, with the device's limit under the
    step's compiled bytes: a problem line that names both, and no numbers
    of the steps, where an allocation failure would end the run."""
    ref, config, v, batches = _case("vit_b16")
    cell = next(w["name"] for w in SPEC["workloads"]
                if w["config"] == "vit_b16" and w["chips"] == 1)
    resolved = harness.resolve_cell(SPEC, cell, tiny=True)
    said = []
    ctx = types.SimpleNamespace(
        config=config, traffic=resolved["traffic"], chips=1, seed=7,
        say=said.append, compiles=types.SimpleNamespace(events=[]))
    first = types.SimpleNamespace(
        done=True, steps=3, batches=batches, before=v,
        losses=[1.0, 1.0, 1.0], after=v["params"],
        moment=jax.tree_util.tree_map(np.zeros_like, v["params"]))
    model = types.SimpleNamespace(
        apply=lambda variables, x, train: ref.forward(variables, x, config))
    monkeypatch.setattr(mode, "free_device_arrays", lambda: 0)
    monkeypatch.setattr(steps, "bytes_limit", lambda: 1 << 20)
    problems, compared = mode.reference_check(ctx, model, v, first)
    assert len(problems) == 1, problems
    assert "the reference's step needs" in problems[0]
    assert "bytes_limit is 1048576" in problems[0]
    assert set(compared) == {"forward_gap"}
    assert not set(train_check.limits(config)) & set(compared)
    # with the limit above the step, the same call compares every number
    monkeypatch.setattr(steps, "bytes_limit", lambda: 1 << 40)
    problems, compared = mode.reference_check(ctx, model, v, first)
    assert set(train_check.limits(config)) <= set(compared)
    assert any("B a parameter" in line for line in said)
