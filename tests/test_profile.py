"""Device-time attribution: op classification, the trace reader on the
recorded v5e trace and on nested events made by hand (innermost op, the
partition, idle labels), the scope maps of the executables a run
registered (every computation, no compile), the step loop's annotations on
the profiler's host plane, the HLO cost model
on the REAL CPU-lowered train step (per-layer scope names included), the
roofline classification boundaries and golden HBM constants, measured-bucket
attribution, the capture analyzer's taint/finalize/error containment, the
zero-sync/zero-compile on-vs-off contract, the roofline gate firing through
regress.compare, and the committed baseline's self-consistency."""

import glob
import json
import os
import re
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.telemetry import events as tme
from tpuic.telemetry.events import EVENT_KINDS, EventBus, MemorySink
from tpuic.telemetry.goodput import (HBM_GBPS, check_flops_drift,
                                     hbm_bandwidth, ridge_intensity,
                                     roofline_intensity, roofline_verdict)
from tpuic.telemetry.profile import (OP_CLASSES, PROFILE_SPECS, UNLABELLED,
                                     UNSCOPED, CaptureAnalyzer,
                                     attribute_device, attribute_device_time,
                                     classify_fusion, classify_op,
                                     hlo_waterfall, layer_of,
                                     hlo_scope_map, metrics_from_event,
                                     parse_trace, scope_path, scope_segments,
                                     train_step_waterfall)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(_REPO, "benchmark", "fixtures",
                       "vit_b16_b64_v5e_2026-07-31.excerpt.xplane.pb")
VERDICTS = {"compute-bound", "hbm-bound", "overhead"}


# -- op classification --------------------------------------------------------
def test_classify_op_table():
    assert classify_op("dot.3") == "matmul"
    assert classify_op("%convolution.5") == "matmul"
    assert classify_op("custom-call.2") == "matmul"  # Pallas entry points
    assert classify_op("reduce.9") == "reduce"
    assert classify_op("reduce-window.1") == "reduce"
    assert classify_op("copy.2") == "copy"
    assert classify_op("transpose.8") == "copy"
    assert classify_op("all-reduce.1") == "collective"
    assert classify_op("get-tuple-element.4") == "overhead"
    assert classify_op("add.77") == "elementwise"
    assert classify_op("rsqrt.3") == "elementwise"
    # Profiler category hints win over the bare name (TPU trace events
    # name fusions without their called computation).
    assert classify_op("fusion.12", "convolution fusion") == "matmul"
    assert classify_op("fusion.7", "loop fusion") == "elementwise"
    assert classify_op("fusion.1", "reduction") == "reduce"


def test_classify_fusion_by_contents():
    assert classify_fusion(["add.1", "dot.2", "multiply.3"]) == "matmul"
    assert classify_fusion(["add.1", "reduce.2"]) == "reduce"
    assert classify_fusion(["copy.1", "transpose.2", "parameter.0"]) == "copy"
    assert classify_fusion(["add.1", "multiply.2"]) == "elementwise"


def test_scope_segments_unwrap_and_layer_of():
    name = ("jit(train_step)/jit(main)/transpose(jvp(Classifier))/"
            "backbone/layer1_0/conv1/conv_general_dilated")
    # jit wrappers drop whole (their payload is a function, not a
    # layer); autodiff wrappers unwrap, so fwd and bwd ops of the same
    # layer share a bucket.
    assert scope_segments(name) == ["Classifier", "backbone", "layer1_0",
                                    "conv1", "conv_general_dilated"]
    assert layer_of(name) == "Classifier/backbone/layer1_0"
    assert layer_of(name, depth=2) == "Classifier/backbone"
    # a scope that is nothing but wrappers has no layer to charge
    assert layer_of("jit(f)/jit(main)") == "(unattributed)"
    # a bare primitive with no module scope rolls up as itself
    assert layer_of("jit(f)/jit(main)/add") == "add"


# -- the trace reader on the recorded chip trace ------------------------------
def test_parse_trace_fixture():
    """The recorded v5e excerpt (``.xplane.pb``, the file every capture
    writes): the program's reader takes the benchmark reducer's window and
    step count and finds the same busy time, to 1e-9 s; with no map every
    op is ``(unscoped)`` under its module, and every view of the busy time
    sums to it."""
    from benchmark.trace_reduce import reduce_trace
    want = reduce_trace(FIXTURE)["devices"][0]
    got = parse_trace(FIXTURE, maps={})
    assert got["source"] == "xplane" and got["device"] == 0
    assert got["steps"] == want["steps"] == 2
    assert got["window_s"] == pytest.approx(want["window_s"], abs=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], abs=1e-9)
    per_step = 1e3 * got["busy_s"] / 2
    assert got["device_ms_per_step"] == pytest.approx(per_step)
    assert list(got["programs"]) == ["jit_train_step"]
    part = got["partition"]["jit_train_step"]
    assert list(part) == [UNSCOPED] and got["scopes"] == {}
    for view in (got["programs"], part, got["classes"]):
        assert sum(view.values()) == pytest.approx(per_step, rel=1e-9)
    # idle: the window less the busy time; inside the step's executions
    # the step's own, between them the launch of an execution the host had
    # dispatched (its PjitFunction event), else under no tpuic.* span (the
    # excerpt predates them)
    assert set(got["idle"]) <= {"inside jit_train_step",
                                "queued jit_train_step", UNLABELLED}
    assert sum(got["idle"].values()) == pytest.approx(
        1e3 * (got["window_s"] - got["busy_s"]) / 2, rel=1e-9)
    # the class of an op with no map is its text's opcode's
    assert got["classes"]["elementwise"] > 0 and got["classes"]["reduce"] > 0
    # a map names the module's role and the ops' scopes
    first = got["ops"][0][1]
    mapped = parse_trace(FIXTURE, maps={"step": {
        "module": "jit_train_step", "ops": {first: (
            "jit(train_step)/jvp(Classifier)/backbone/block0/mlp/dot_general",
            "fusion", "matmul")}}})
    assert list(mapped["programs"]) == ["step"]
    assert mapped["scopes"]["mlp"] == pytest.approx(got["ops"][0][3])
    assert set(mapped["unmapped"]["step"]) == (
        set(got["unmapped"]["jit_train_step"]) - {first})


def test_parse_trace_cpu_capture_is_none(tmp_path):
    """A capture with no device plane (every CPU capture) must say so —
    None — instead of fabricating a waterfall from host events."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert parse_trace(str(tmp_path), maps={}) is None
    assert parse_trace(str(tmp_path / "nothing-here")) is None


# -- device time by scope: innermost attribution, partition, idle labels ------
def _ms(t):
    return 1e-3 * t          # events in seconds, read back in ms a step


def _nested_run():
    """Step executions at 0, 20, 40, 60 ms (10 ms each) and a prep after
    each: in a step a ``while`` over a rotary op and a compiler's copy,
    and a ``conditional`` whose branch runs a matmul and a custom call a
    compiler pass made without metadata, then the optimizer and a copy
    with no metadata; the prep's op has the step's first op's name."""
    modules, ops = [], []
    for b in (0, 20, 40, 60):
        modules += [("jit_step(7)", _ms(b), _ms(10)),
                    ("jit_prep(8)", _ms(b + 12), _ms(2))]
        ops += [("%while.1 = (f32[]) while((f32[]) %t)", _ms(b), _ms(8)),
                ("%fusion.2 = f32[8] fusion(f32[8] %p)", _ms(b + 1), _ms(2)),
                ("%conditional.3 = f32[8] conditional(s32[] %i)", _ms(b + 4),
                 _ms(3)),
                ("%dot.4 = f32[8] dot(f32[8] %a)", _ms(b + 4.5), _ms(1.5)),
                ("%custom-call.7 = f32[8] custom-call(f32[8] %a)", _ms(b + 6),
                 _ms(0.5)),
                ("%copy-done.8 = f32[8] copy-done((f32[8]) %s)", _ms(b + 3),
                 _ms(0.5)),
                ("%fusion.5 = f32[8] fusion(f32[8] %u)", _ms(b + 8), _ms(1)),
                ("%copy.6 = f32[8] copy(f32[8] %c)", _ms(b + 9), _ms(1)),
                ("%fusion.2 = f32[8] fusion(u8[8] %x)", _ms(b + 12), _ms(1))]
    body = "jit(step)/jvp(Classifier)/backbone/while/body/closed_call/block0"
    maps = {"step": {"module": "jit_step", "ops": {
        "while.1": ("jit(step)/jvp(Classifier)/backbone/while", "while",
                    "overhead"),
        "fusion.2": (body + "/attn/rotary/mul", "fusion", "elementwise"),
        "conditional.3": (body + "/moe/routed_experts/cond", "conditional",
                          "overhead"),
        "dot.4": (body + "/moe/routed_experts/cond/branch_1_fun/"
                  "expert_matmul/dot_general", "dot", "matmul"),
        "fusion.5": ("jit(step)/optimizer_update/add", "fusion",
                     "elementwise"),
        "copy.6": ("", "copy", "copy"),
        "custom-call.7": ("", "custom-call", "matmul"),
        "copy-done.8": ("", "copy-done", "copy")},
        "inferred": {"fusion.5": "last"}},
        "input_prep": {"module": "jit_prep", "ops": {
            "fusion.2": ("jit(prep)/add", "fusion", "elementwise")}}}
    return modules, ops, maps


def test_innermost_attribution_and_the_partition_sums_to_busy_time():
    modules, ops, maps = _nested_run()
    r = attribute_device(modules, ops, maps=maps, skip_first=0)
    assert (r["steps"], r["window_s"]) == (3, pytest.approx(_ms(60)))
    step = r["partition"]["step"]
    # a while and a conditional keep only the time no op of theirs covers;
    # an op made without metadata takes the scope of the op around it,
    # but for the compiler's copies
    assert step == pytest.approx({
        "Classifier/backbone": 2.5,
        "Classifier/backbone/block0/attn/rotary": 2.0,
        "Classifier/backbone/block0/moe/routed_experts": 1.0 + 0.5,
        "Classifier/backbone/block0/moe/routed_experts/expert_matmul": 1.5,
        "optimizer_update": 1.0, UNSCOPED: 1.0 + 0.5})
    assert r["partition"]["input_prep"] == pytest.approx({UNSCOPED: 1.0})
    assert r["programs"] == pytest.approx({"step": 10.0, "input_prep": 1.0})
    # every view of the busy time sums to it exactly
    busy = 1e3 * r["busy_s"] / 3
    assert busy == pytest.approx(11.0)
    assert sum(v for p in r["partition"].values() for v in p.values()) \
        == pytest.approx(busy, rel=1e-12)
    assert sum(r["programs"].values()) == pytest.approx(busy, rel=1e-12)
    assert sum(r["classes"].values()) == pytest.approx(busy, rel=1e-12)
    assert r["classes"] == pytest.approx({"overhead": 3.5, "elementwise": 4.0,
                                          "matmul": 2.0, "copy": 1.5})
    # a scope holds its children
    assert r["scopes"]["routed_experts"] == pytest.approx(3.0)
    assert r["scopes"]["expert_matmul"] == pytest.approx(1.5)
    assert r["scopes"]["rotary"] == pytest.approx(2.0)
    assert r["scopes"]["backbone"] == pytest.approx(7.5)
    # what ops without metadata of their own were charged, by rule
    assert set(r["inferred"]) == {"encloser", "last"}
    assert r["inferred"]["encloser"] == pytest.approx(dict.fromkeys(
        ("Classifier", "backbone", "block0", "moe", "routed_experts"), 0.5))
    assert r["inferred"]["last"] == pytest.approx({"optimizer_update": 1.0})
    assert r["unmapped"] == {}
    assert sum(r["idle"].values()) == pytest.approx(9.0)
    # no map: the module names the program, the op's text its class
    bare = attribute_device(modules, ops, skip_first=0)
    assert bare["programs"] == pytest.approx({"jit_step": 10.0,
                                              "jit_prep": 1.0})
    assert bare["classes"]["matmul"] == pytest.approx(2.0)  # dot, call
    assert bare["unmapped"] == {"jit_step": [
        "conditional.3", "copy-done.8", "copy.6", "custom-call.7", "dot.4",
        "fusion.2", "fusion.5", "while.1"], "jit_prep": ["fusion.2"]}


def test_an_op_that_outlasts_its_parent_is_charged_once():
    ops = [("%a = f32[] add()", 0.0, 4.0), ("%b = f32[] add()", 1.0, 5.0),
           ("%c = f32[] add()", 2.0, 1.0), ("%d = f32[] add()", 8.0, 1.0)]
    r = attribute_device([], ops, skip_first=0)
    # [0, 6] + [8, 9]: the union, each instant to the latest-started op
    assert r["busy_s"] == pytest.approx(7.0)
    assert sum(v for _, _, _, v in r["ops"]) == pytest.approx(7e3)
    assert {op: v for _, op, _, v in r["ops"]} == pytest.approx(
        {"a": 1e3, "b": 5e3 - 1e3, "c": 1e3, "d": 1e3})


def test_idle_gaps_are_named_by_the_innermost_tpuic_annotation():
    """A gap between executions takes the host's innermost ``tpuic.*``
    annotation over its midpoint; one inside an execution is the program's
    own wait, and one before an execution the host had already dispatched
    the device's launch of it, whatever the host was doing."""
    modules = [("jit_step(7)", _ms(b), _ms(10)) for b in (0, 20, 40, 60)]
    ops = [(f"%fusion.{i} = f32[8] fusion(f32[8] %p)", _ms(b + s), _ms(d))
           for b in (0, 20, 40, 60) for i, (s, d) in enumerate(((0, 4),
                                                                (5, 5)))]
    notes = [("tpuic.train_epoch", 0.0, _ms(50)),
             ("tpuic.step.drain", _ms(14), _ms(2)),
             ("tpuic.step.next", _ms(33), _ms(4)),
             ("tpuic.step.dispatch", _ms(4), _ms(2))]
    maps = {"step": {"module": "jit_step", "ops": {}}}
    r = attribute_device(modules, ops, notes, maps, skip_first=0)
    # gaps [4,5] [10,20] [24,25] [30,40] [44,45] [50,60], by midpoint
    assert r["idle"] == pytest.approx({
        "inside step": 1.0, "tpuic.step.drain": 10 / 3,
        "tpuic.step.next": 10 / 3, UNLABELLED: 10 / 3})
    # the host's dispatches, matched to the executions from the last back:
    # the one at 40 was sent at 27, before the device went idle at 30
    sent = [("jit_step", _ms(t), _ms(1)) for t in (-5, 14, 26, 55)]
    q = attribute_device(modules, ops, notes, maps, skip_first=0,
                         dispatches=sent)
    assert q["idle"] == pytest.approx({
        "inside step": 1.0, "tpuic.step.drain": 10 / 3,
        "queued step": 10 / 3, UNLABELLED: 10 / 3})
    assert sorted(g[1] for g in r["gaps"]) == pytest.approx([1.0] * 3
                                                            + [10.0] * 3)
    assert r["gaps"][0][2] in ("tpuic.step.drain", "tpuic.step.next",
                               UNLABELLED)


# -- the scope map of the executable a step dispatched ------------------------
_FAMILY_SCOPES = {       # both ways: forward and backward ops under each
    "vit-tiny": ("attention_core",),
    "ouro-tiny": ("rotary", "attention_core"),
    "kanana-tiny": ("rotary", "attention_core", "routed_experts"),
    "mellum-tiny": ("rotary", "attention_core", "routed_experts"),
    "resnet18-cifar": ("stem",),
}


@pytest.mark.parametrize("name", sorted(_FAMILY_SCOPES))
def test_scope_map_of_the_compiled_tiny_step(name):
    """Built from the registered step's executable: every instruction of
    every computation, and the family's scopes on ops of the forward and
    of the backward — through ``rotate``'s hand-written VJP, remat's
    recomputation, the looped stack's ``scan`` body and the routed sum's
    ``cond`` branches — with the step's own ``augment`` and
    ``optimizer_update``."""
    from tpuic.config import ModelConfig, OptimConfig
    from tpuic.models import create_model_from_config, family
    from tpuic.telemetry.profile import Programs
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import create_train_state
    from tpuic.train.step import make_train_step
    remat = "blocks" in family(name).remat_policies
    mc = ModelConfig(name=name, num_classes=10, dtype="float32", remat=remat,
                     remat_policy="blocks")
    oc = OptimConfig(optimizer="adam", class_weights=(), milestones=(),
                     random_erase=0.25)
    model = create_model_from_config(mc)
    tx = make_optimizer(oc, 8, 1, global_batch=2)
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, jax.random.key(0), (2, 32, 32, 3)))
    sds = jax.ShapeDtypeStruct
    batch = {"image": sds((2, 32, 32, 3), jnp.float32),
             "label": sds((2,), jnp.int32), "mask": sds((2,), jnp.float32)}
    progs = Programs()
    progs.note("step", make_train_step(oc, mc, donate=False), (state, batch))
    m = progs.scope_map("step")
    text = progs.compiled("step").as_text()
    assert m["module"] == "jit_train_step"
    assert set(m["ops"]) == set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ",
                                           text, re.M))
    names = [op_name for op_name, _, _ in m["ops"].values()]
    for scope in _FAMILY_SCOPES[name]:
        under = [n for n in names if scope in scope_path(n)]
        assert any("transpose(" not in n for n in under), (name, scope)
        assert any("transpose(" in n for n in under), (name, scope)
    for scope in ("augment", "optimizer_update"):
        assert any(scope in scope_path(n) for n in names), (name, scope)
    # the body of the loop and the branches of the routed sum are there
    if name == "ouro-tiny":
        assert any("while/body" in n and "rotary" in n for n in names)
    if name in ("kanana-tiny",):
        assert any("branch_" in n and "routed_experts" in n for n in names)
    assert {cls for _, _, cls in m["ops"].values()} <= set(OP_CLASSES)


_HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.7 (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %multiply.3 = f32[8]{0} multiply(f32[8]{0} %p0, f32[8]{0} %p1), metadata={op_name="jit(train_step)/optimizer_update/mul"}
  %add.4 = f32[8]{0} add(f32[8]{0} %multiply.3, f32[8]{0} %p0), metadata={op_name="jit(train_step)/optimizer_update/add"}
  %select.6 = f32[8]{0} select(pred[8]{0} %p1, f32[8]{0} %add.4, f32[8]{0} %p0), metadata={op_name="jit(train_step)/select_n"}
  ROOT %tuple.5 = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %select.6, f32[8]{0} %multiply.3)
}

%fused_computation.8 (q0: f32[8]) -> f32[8] {
  %q0 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(f32[8]{0} %q0), metadata={op_name="jit(train_step)/transpose(jvp(Classifier))/head/neg"}
  ROOT %add.2 = f32[8]{0} add(f32[8]{0} %negate.1, f32[8]{0} %q0), metadata={op_name="jit(train_step)/optimizer_update/add"}
}

%branch_0.1 (r0: f32[8]) -> f32[8] {
  %r0 = f32[8]{0} parameter(0)
  ROOT %gather.1 = f32[8]{0} negate(f32[8]{0} %r0), metadata={op_name="jit(train_step)/jvp(Classifier)/moe/routed_experts/dispatch/gather"}
}

%branch_1.2 (s0: f32[8]) -> f32[8] {
  %s0 = f32[8]{0} parameter(0)
  %ragged-dot.3 = f32[8]{0} custom-call(f32[8]{0} %s0), custom_call_target="ragged_dot"
  ROOT %scatter.4 = f32[8]{0} negate(f32[8]{0} %ragged-dot.3), metadata={op_name="jit(train_step)/jvp(Classifier)/moe/routed_experts/combine/scatter-add"}
}

ENTRY %main.9 (a: f32[8], b: f32[8]) -> (f32[8], f32[8]) {
  %a = f32[8]{0} parameter(0)
  %b = f32[8]{0} parameter(1)
  %dot.1 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %b), metadata={op_name="jit(train_step)/jvp(Classifier)/head/dot_general"}
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %dot.1)
  %copy-done.3 = f32[8]{0} copy-done((f32[8]{0}, f32[8]{0}, u32[]) %copy-start.2)
  %fusion.8 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(train_step)/jit(_where)/select_n"}
  %i = s32[] constant(0)
  %conditional.5 = f32[8]{0} conditional(s32[] %i, f32[8]{0} %a, f32[8]{0} %b), branch_computations={%branch_0.1, %branch_1.2}
  ROOT %fusion.7 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %copy-done.3, f32[8]{0} %b), kind=kLoop, calls=%fused_computation.7
}
"""


def test_an_op_without_a_scope_takes_what_it_runs():
    """A multi-output fusion's root is a tuple, which carries no metadata:
    the fusion is charged to the scopes its computation's scoped ops share
    (here the optimizer's, beside the guard's select); the compiler's
    copies stay without."""
    m = hlo_scope_map(_HLO)
    assert m["module"] == "jit_train_step"
    assert m["ops"]["fusion.7"] == (
        "optimizer_update/fusion", "fusion", "elementwise")
    assert scope_path(m["ops"]["fusion.7"][0]) == ["optimizer_update"]
    # a root whose metadata names no scope, over ops of two scopes: the
    # last's, the nearest the root
    assert scope_path(m["ops"]["fusion.8"][0]) == ["optimizer_update"]
    # a conditional with no metadata: what its branches share
    assert scope_path(m["ops"]["conditional.5"][0]) == [
        "Classifier", "moe", "routed_experts"]
    # and each such instruction is named with the rule that gave it
    assert m["inferred"] == {"fusion.7": "shared", "fusion.8": "last",
                             "conditional.5": "shared"}
    assert m["ops"]["ragged-dot.3"] == ("", "custom-call", "matmul")
    assert m["ops"]["dot.1"][1:] == ("dot", "matmul")
    assert m["ops"]["copy-done.3"] == ("", "copy-done", "copy")
    assert m["ops"]["tuple.5"][1:] == ("tuple", "overhead")
    assert set(m["ops"]) == {
        "p0", "p1", "multiply.3", "add.4", "select.6", "tuple.5", "q0",
        "negate.1", "add.2", "r0", "gather.1", "s0", "ragged-dot.3",
        "scatter.4", "a", "b", "dot.1", "copy-start.2", "copy-done.3",
        "fusion.8", "i", "conditional.5", "fusion.7"}


# -- the programs of a real run, and the step loop's phases on the profiler ---
@pytest.fixture(scope="module", params=[1, 4], ids=["data1", "data4"])
def profiled_run(request, imagefolder, tmp_path_factory):
    """A two-epoch CPU Trainer run, the second epoch under the profiler,
    with a registry of its own: on one device, and on a ``data`` mesh of
    four, whose step is one sharded program."""
    chips = request.param
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.runtime.mesh import make_mesh
    from tpuic.telemetry import profile
    from tpuic.train.loop import Trainer
    tmp = tmp_path_factory.mktemp("profiled")
    saved, profile.programs = profile.programs, profile.Programs()
    try:
        cfg = Config(
            data=DataConfig(data_dir=imagefolder, resize_size=32,
                            batch_size=4 // chips, num_workers=1,
                            shuffle_seed=0),
            model=ModelConfig(name="vit-tiny", num_classes=0,
                              dtype="float32"),
            optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                              class_weights=(), milestones=()),
            run=RunConfig(epochs=2, ckpt_dir=str(tmp / "cp"), save_period=1,
                          resume=False, log_every_steps=2),
            mesh=MeshConfig())
        trainer = Trainer(cfg, mesh=make_mesh(MeshConfig(data=chips),
                                              devices=jax.devices()[:chips]))
        trainer.train_epoch(0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            trainer.train_epoch(1)
            jax.block_until_ready(trainer.state)
        finally:
            jax.profiler.stop_trace()
        trainer.telemetry.close()
        yield {"programs": profile.programs, "trainer": trainer,
               "trace": str(tmp / "trace"), "chips": chips}
    finally:
        profile.programs = saved


def test_the_maps_after_a_dispatch_compile_nothing_and_hold_no_array(
        profiled_run):
    progs = profiled_run["programs"]
    assert set(progs.roles()) == {"step", "input_prep"}
    for role in progs.roles():
        leaves = jax.tree_util.tree_leaves(progs._held[role].specs)
        assert leaves and not any(isinstance(x, jax.Array) for x in leaves)
    # the step's arguments keep the shardings they were dispatched with, so
    # the lookup finds the executable of the mesh that ran
    step_specs = jax.tree_util.tree_leaves(progs._held["step"].specs)
    assert max((len(s.sharding.device_set) for s in step_specs
                if getattr(s, "sharding", None) is not None),
               default=1) == profiled_run["chips"]
    tme.install_jax_compile_listener()
    seen = []
    unsubscribe = tme.subscribe(lambda ev: seen.append(ev.data["key"]),
                                kinds=("compile",))
    try:
        maps = progs.scope_maps()
    finally:
        unsubscribe()
    assert "backend_compile_duration" not in seen, seen
    assert maps["step"]["module"] == "jit_train_step"
    assert maps["input_prep"]["module"] == "jit_resident_prep"
    assert any("optimizer_update" in scope_path(n)
               for n, _, _ in maps["step"]["ops"].values())
    # the registration held the compiled step, not the stand-in of a test
    assert progs._held["step"].fn is profiled_run["trainer"].train_step


def test_a_profiled_run_puts_the_step_phases_on_the_host_plane(profiled_run):
    from tpuic.telemetry.profile import find_xplane, read_xplane
    path = find_xplane(profiled_run["trace"])
    raw = read_xplane(path)
    names = {n for n, _, _ in raw["annotations"]}
    # JAX's own dispatch events, by the module each runs
    assert {"jit_train_step", "jit_resident_prep"} <= {
        n for n, _, _ in raw["dispatches"]}
    assert {"tpuic.step.next", "tpuic.step.dispatch", "tpuic.step.drain",
            "tpuic.step.end", "tpuic.train_epoch", "tpuic.epoch.head"} <= names
    assert all(n.startswith("tpuic.") for n in names)
    # a CPU capture: the program's reader finds no device plane
    assert parse_trace(path) is None


# -- roofline math (golden constants + boundaries) ----------------------------
def test_hbm_table_golden_values():
    """Pinned like the PEAK_FLOPS table: these are public spec-sheet
    numbers every roofline verdict is judged against."""
    assert HBM_GBPS["TPU v5e"] == 819
    assert HBM_GBPS["TPU v5"] == 2765
    assert HBM_GBPS["TPU v4"] == 1228
    assert HBM_GBPS["cpu"] == 50
    assert hbm_bandwidth(None) == 50e9
    assert hbm_bandwidth(jax.devices()[0]) == 50e9  # CPU CI


def test_unknown_device_kind_has_no_bandwidth():
    """A device the table does not know is an error, not 50 GB/s."""
    class _Dev:
        device_kind = "QPU v1"

    with pytest.raises(ValueError, match="unknown device kind 'QPU v1'"):
        hbm_bandwidth(_Dev())


def test_roofline_classification_boundaries():
    peak, bw = 100e12, 1e12   # ridge = 100 FLOPs/byte
    assert ridge_intensity(peak, bw) == 100.0
    assert roofline_intensity(200.0, 2.0) == 100.0
    assert roofline_intensity(1.0, 0.0) is None
    # exactly AT the ridge counts as compute-bound (>=)
    assert roofline_verdict(100.0, 1.0, peak, bw) == "compute-bound"
    assert roofline_verdict(99.0, 1.0, peak, bw) == "hbm-bound"
    assert roofline_verdict(101.0, 1.0, peak, bw) == "compute-bound"
    # neither axis exercised -> overhead; flops with no bytes -> compute
    assert roofline_verdict(0.0, 0.0, peak, bw) == "overhead"
    assert roofline_verdict(5.0, 0.0, peak, bw) == "compute-bound"
    assert roofline_verdict(0.0, 5.0, peak, bw) == "hbm-bound"


def test_check_flops_drift_warns_past_tolerance():
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # within 10%: silent
        d = check_flops_drift("resnet50", 224, 8,
                              1.05 * 3 * 8.2e9 * 8)
        assert d == pytest.approx(0.05, abs=0.01)
    seen = []
    d = check_flops_drift("resnet50", 224, 8, 2 * 3 * 8.2e9 * 8,
                          warn=seen.append)
    assert d == pytest.approx(0.5)
    assert len(seen) == 1 and "drifts" in seen[0]
    assert check_flops_drift("no-such-model", 224, 8, 1e9) is None
    assert check_flops_drift("resnet50", 224, 8, 0.0) is None


# -- HLO cost model on the real train step ------------------------------------
def test_hlo_waterfall_real_train_step_and_scope_names():
    """Cost-analysis extraction on the real CPU-lowered train step: the
    classes exist with verdicts, matmul carries the FLOPs, and the
    per-layer scope names (flax module paths + the jax.named_scope tags
    threaded through the model zoo and step functions) appear in the
    lowered HLO and the layer rollup."""
    wf = train_step_waterfall("resnet18-cifar", 32, 2)
    assert wf["source"] == "hlo_cost_model"
    c = wf["classes"]
    assert c["matmul"]["flops"] > 1e9          # fwd+bwd conv/dot flops
    assert c["matmul"]["ms"] > 0
    for name, cls in c.items():
        assert cls["verdict"] in VERDICTS, (name, cls)
        assert name in OP_CLASSES
    # cost_analysis total flows through (and the drift cross-check ran)
    assert wf["total_flops"] > 1e9
    assert "analytic_flops_drift" in wf
    ly = wf["layers"]
    assert any("layer1_0" in k for k in ly), ly
    assert any("stem" in k for k in ly), ly       # jax.named_scope tag
    # time concentrates where the channels are (layer4 >> layer1)
    l4 = sum(v for k, v in ly.items() if "layer4" in k)
    l1 = sum(v for k, v in ly.items() if "layer1" in k and "bn" not in k)
    assert l4 > l1
    # the modeled class times sum to the modeled total
    assert sum(cl["ms"] for cl in c.values()) == pytest.approx(
        wf["modeled_ms_total"], rel=0.01)


def test_named_scopes_in_compiled_hlo_vit():
    """The ViT structural scopes (tokenize/cls_pool/attention_core) land
    in compiled-HLO op metadata — the paths the waterfall rolls up by."""
    from tpuic.models import create_model
    m = create_model("vit-tiny", 10, dtype="float32")
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    v = m.init(jax.random.key(0), x, train=False)
    text = jax.jit(lambda v, x: m.apply(v, x, train=False)).lower(
        v, x).compile().as_text()
    for scope in ("tokenize", "cls_pool", "attention_core"):
        assert scope in text, scope


# -- measured-bucket attribution ----------------------------------------------
def _tiny_model_wf():
    return {"source": "hlo_cost_model", "modeled_ms_total": 8.0,
            "peak_flops": 1e12, "hbm_bytes_per_s": 50e9,
            "ridge_intensity": 20.0, "total_flops": 6e9,
            "classes": {
                "matmul": {"ms": 6.0, "frac": 0.75, "flops": 6e9,
                           "bytes": 1e8, "ops": 3, "intensity": 60.0,
                           "verdict": "compute-bound"},
                "copy": {"ms": 2.0, "frac": 0.25, "flops": 0.0,
                         "bytes": 1e8, "ops": 2, "intensity": 0.0,
                         "verdict": "hbm-bound"}},
            "layers": {"a/b": 6.0, "a/c": 2.0}}


def test_attribute_device_time_sums_to_measured_mean():
    out = attribute_device_time(_tiny_model_wf(), [10.0, 10.0, 40.0])
    assert out["device_ms_best"] == 10.0
    assert out["device_ms_per_step"] == 20.0
    assert out["stall_ms"] == 10.0
    # modeled 8 ms scales onto the best step (10 ms): matmul 7.5, copy
    # 2.5; the mean-over-best excess books to overhead.
    assert out["classes"]["matmul"]["ms"] == pytest.approx(7.5)
    assert out["classes"]["copy"]["ms"] == pytest.approx(2.5)
    assert out["classes"]["overhead"]["ms"] == pytest.approx(10.0)
    assert out["classes"]["overhead"]["verdict"] == "overhead"
    # THE acceptance invariant: per-class times sum to the measured mean
    assert sum(c["ms"] for c in out["classes"].values()) == pytest.approx(
        out["device_ms_per_step"], rel=0.001)
    # fractions renormalized over the measured total
    assert sum(c["frac"] for c in out["classes"].values()) == pytest.approx(
        1.0, abs=0.01)
    # layers scale with the program-time anchor
    assert out["layers"]["a/b"] == pytest.approx(7.5)
    # no measured steps: the model passes through untouched
    assert attribute_device_time(_tiny_model_wf(), [])["classes"][
        "matmul"]["ms"] == 6.0


# -- capture analyzer ---------------------------------------------------------
def _provider_tiny():
    """A minimal real compiled program as the HLO source."""
    f = jax.jit(lambda x: (x @ x).sum())
    compiled = f.lower(jnp.ones((32, 32), jnp.float32)).compile()
    from tpuic.telemetry.goodput import cost_analysis_dict
    return compiled.as_text(), cost_analysis_dict(compiled)


def test_capture_analyzer_taint_finalize_and_event():
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)
    an = CaptureAnalyzer(hlo_provider=_provider_tiny, peak=1e12,
                         hbm_bytes_per_s=50e9, bus=bus, warmup_steps=0)
    bus.subscribe(an.on_event, kinds=("step", "trace"))

    def step(n, device_ms):
        bus.publish("step", step=n, total_ms=device_ms + 1.0, data_ms=0.5,
                    dispatch_ms=0.5, device_ms=device_ms)
    step(1, 10.0)
    bus.publish("trace", action="started", path="t")
    step(2, 500.0)   # inside the window: tainted
    step(3, 500.0)
    bus.publish("trace", action="stopped", path="t")
    step(4, 300.0)   # absorbed the stop/serialize: tainted
    step(5, 12.0)
    step(6, 14.0)
    an.finalize()
    assert an.tainted_steps == 3
    evs = ms.of("profile")
    assert len(evs) == 1 and evs[0].data["final"]
    d = evs[0].data
    assert d["tainted_steps_excluded"] == 3
    assert d["steps"] == 3              # steps 1, 5, 6 only
    assert d["device_ms_per_step"] == pytest.approx(12.0, abs=0.01)
    assert sum(c["ms"] for c in d["classes"].values()) == pytest.approx(
        d["device_ms_per_step"], rel=0.01)
    for c in d["classes"].values():
        assert c["verdict"] in VERDICTS
    # "profile" is a typed event kind
    assert "profile" in EVENT_KINDS


def test_capture_analyzer_error_contained():
    """A broken HLO provider publishes an error field — it must never
    raise into the capture/finalize path (tracing.py discipline)."""
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)

    def broken():
        raise RuntimeError("no HLO for you")
    an = CaptureAnalyzer(hlo_provider=broken, bus=bus)
    bus.subscribe(an.on_event, kinds=("step",))
    bus.publish("step", step=1, device_ms=5.0)
    an.finalize()          # must not raise
    an.on_capture("/nonexistent/trace/dir")  # must not raise
    evs = ms.of("profile")
    assert len(evs) == 2
    assert all("no HLO for you" in e.data["error"] for e in evs)
    assert an.last is None


def test_trace_trigger_on_capture_hook_and_analyze_error(tmp_path):
    """The tracing.py satellite: a closed window invokes on_capture with
    the capture path; a hook failure publishes analyze_error and does
    NOT disable the trigger (capture failure semantics unchanged)."""
    from tpuic.telemetry.tracing import TraceTrigger
    bus = EventBus()
    ms = MemorySink()
    bus.subscribe(ms)
    seen = []

    def hook(path):
        seen.append(path)
        raise RuntimeError("analyzer exploded")
    trig = TraceTrigger(str(tmp_path / "tr"), threshold=0.0, trace_steps=1,
                        cooldown=0, bus=bus, force_first=True,
                        on_capture=hook)
    trig.observe(0.01)   # force_first: window opens
    trig.observe(0.01)   # window of 1 step closes -> hook fires
    assert len(seen) == 1 and seen[0].startswith(str(tmp_path / "tr"))
    actions = [e.data["action"] for e in ms.of("trace")]
    assert actions.count("analyze_error") == 1
    assert "stopped" in actions
    assert not trig._disabled    # analysis failure never stands down
    trig._force = True
    trig.observe(0.01)
    trig.observe(0.01)
    assert len(seen) == 2        # still capturing AND still analyzing


# -- the PR-2 discipline: no new syncs, no new compiles -----------------------
def test_analyzer_zero_syncs_zero_compiles_on_vs_off():
    """The on-vs-off equality check every telemetry module carries: the
    analyzer's step intake adds no device_gets and no compiles."""
    from tpuic.analysis import runtime as contracts

    def loop(with_analyzer):
        bus = EventBus()
        an = None
        if with_analyzer:
            an = CaptureAnalyzer(bus=bus)
            bus.subscribe(an.on_event, kinds=("step", "trace"))

        @jax.jit
        def step(s, x):
            s = s + x.sum()
            return s, {"loss": s}
        with contracts.count_device_gets() as gets:
            state = jnp.zeros(())
            for i in range(6):
                state, m = step(state, jnp.ones((4,)) * i)
                jax.device_get({"loss": m["loss"]})
                bus.publish("step", step=i + 1, total_ms=5.0, data_ms=1.0,
                            dispatch_ms=0.1, device_ms=3.9)
        return step, gets.count

    step_off, gets_off = loop(False)
    step_on, gets_on = loop(True)
    assert gets_on == gets_off == 6
    assert contracts.jit_cache_size(step_off) == 1
    assert contracts.jit_cache_size(step_on) == 1


# -- the roofline gate --------------------------------------------------------
def test_roofline_gate_fires_on_class_shift():
    """PROFILE_SPECS through regress.compare (the shared tolerance
    machinery): a clean fresh passes, a stall-shifted distribution
    regresses naming frac_overhead."""
    from tpuic.telemetry.regress import compare
    baseline = {"schema": 1, "calibration_s": 0.01, "metrics": {
        "profile.frac_matmul": {"value": 0.55, "noise": 0.05},
        "profile.frac_copy": {"value": 0.26, "noise": 0.05},
        "profile.frac_overhead": {"value": 0.13, "noise": 0.1},
        "profile.device_ms_per_step": {"value": 9.0, "noise": 0.1}}}
    clean = {"profile.frac_matmul": 0.53, "profile.frac_copy": 0.27,
             "profile.frac_overhead": 0.16,
             "profile.device_ms_per_step": 9.8}
    rep = compare(baseline, clean, 0.01, specs=PROFILE_SPECS)
    assert not rep["regressed"], rep
    shifted = {"profile.frac_matmul": 0.03, "profile.frac_copy": 0.01,
               "profile.frac_overhead": 0.95,
               "profile.device_ms_per_step": 200.0}
    rep = compare(baseline, shifted, 0.01, specs=PROFILE_SPECS)
    assert rep["regressed"]
    assert "profile.frac_overhead" in rep["regressed_metrics"]
    assert "profile.device_ms_per_step" in rep["regressed_metrics"]


def test_metrics_from_event():
    ev = {"classes": {"matmul": {"frac": 0.5}, "copy": {"frac": 0.2},
                      "overhead": {"frac": 0.3}},
          "device_ms_per_step": 12.5}
    m = metrics_from_event(ev)
    assert m == {"profile.frac_matmul": 0.5, "profile.frac_copy": 0.2,
                 "profile.frac_overhead": 0.3,
                 "profile.device_ms_per_step": 12.5}
    # absent classes read as 0 (a run with no stall must still gate)
    m = metrics_from_event({"classes": {"matmul": {"frac": 1.0}}})
    assert m["profile.frac_overhead"] == 0.0


def test_committed_roofline_baseline_selfconsistent():
    """The committed artifact IS the acceptance claim: per-op-class
    times sum to within 5% of the recorded device bucket and every
    class carries a roofline verdict."""
    path = os.path.join(_REPO, "perf", "roofline_baseline.json")
    with open(path) as f:
        b = json.load(f)
    for name in PROFILE_SPECS:
        assert name in b["metrics"], name
    wf = b["waterfall"]
    assert wf["final"]
    total = sum(c["ms"] for c in wf["classes"].values())
    assert total == pytest.approx(wf["device_ms_per_step"], rel=0.05)
    for name, c in wf["classes"].items():
        assert c["verdict"] in VERDICTS, (name, c)
    assert wf["classes"]["matmul"]["verdict"] == "compute-bound"


# -- prom exposition ----------------------------------------------------------
def test_prom_profile_rows_on_both_expositions():
    from tpuic.telemetry.goodput import GoodputTracker
    from tpuic.telemetry.prom import (profile_rows, render,
                                      serve_exposition, train_exposition)
    wf = attribute_device_time(_tiny_model_wf(), [10.0, 12.0])
    text = render(profile_rows(wf))
    assert 'device_time_ms{op_class="matmul"}' in text
    assert 'device_time_frac{op_class="overhead"}' in text
    assert 'roofline_verdict{op_class="matmul"} 1' in text
    assert 'roofline_verdict{op_class="copy"} 0' in text
    assert "device_ms_per_step" in text
    gt = GoodputTracker(flops_per_step=1e9, peak_flops=1e12)
    gt.start()
    t = train_exposition(gt.report(), profile=wf)
    assert 'tpuic_train_device_time_ms{op_class="matmul"}' in t
    assert train_exposition(gt.report())  # None profile renders nothing
    assert "device_time_ms" not in train_exposition(gt.report())
    from tpuic.serve.metrics import ServeStats
    s = ServeStats()
    s.record_cost(8, 1e9, 1e7)
    text = serve_exposition(s.snapshot(), profile=wf)
    assert 'tpuic_serve_device_time_ms{op_class="matmul"}' in text
    assert 'tpuic_serve_executable_flops{bucket="8"} 1e+09' in text
    assert 'tpuic_serve_executable_intensity{bucket="8"} 100' in text


# -- serve engine cost capture ------------------------------------------------
def test_serve_engine_cost_analysis_and_waterfall():
    """The AOT bucket executables expose cost_analysis where the runtime
    provides it: recorded per bucket at compile, rendered as roofline
    context, and the engine can produce a device-time waterfall scaled
    to the span ledger's measured device phase."""
    from tpuic.serve import InferenceEngine
    size = 8

    def fwd(variables, images):
        x = images.astype(jnp.float32).reshape(images.shape[0], -1)
        w = jnp.ones((x.shape[1], 4), jnp.float32)
        return jax.nn.softmax(x @ w, axis=-1)

    eng = InferenceEngine(forward_fn=fwd, variables={}, image_size=size,
                          input_dtype=np.uint8, buckets=(1, 4),
                          max_wait_ms=1.0)
    try:
        eng.warmup()
        cost = eng.stats.snapshot()["executable_cost"]
        assert set(cost) == {"1", "4"}
        assert cost["4"]["flops"] > 0 and cost["4"]["bytes"] > 0
        assert cost["4"]["intensity"] is not None
        # before any traffic: the model-only waterfall
        wf = eng.profile_waterfall()
        assert wf is not None and wf["bucket"] == 4
        assert set(wf["classes"]) <= set(OP_CLASSES)
        # after traffic the span ledger's device phase anchors it
        rng = np.random.default_rng(0)
        for _ in range(3):
            eng.predict(rng.integers(0, 256, (2, size, size, 3), np.uint8))
        wf = eng.profile_waterfall()
        assert wf["source"].endswith("+measured")
        assert sum(c["ms"] for c in wf["classes"].values()) == \
            pytest.approx(wf["device_ms_per_step"], rel=0.01)
    finally:
        eng.close()


# -- end-to-end through the Trainer (slow; CI profile smoke also covers) ------
@pytest.mark.slow
def test_trainer_trace_analyze_end_to_end(imagefolder, tmp_path,
                                          monkeypatch):
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.train.loop import Trainer
    monkeypatch.setenv("TPUIC_TRACE", str(tmp_path / "traces"))
    jsonl = str(tmp_path / "events.jsonl")
    cfg = Config(
        data=DataConfig(data_dir=imagefolder, resize_size=32, batch_size=2,
                        num_workers=2, shuffle_seed=0),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=3, ckpt_dir=str(tmp_path / "cp"),
                      save_period=1, resume=False, log_every_steps=1,
                      max_steps=10, metrics_jsonl=jsonl,
                      trace_analyze=True),
        mesh=MeshConfig(),
    )
    trainer = Trainer(cfg)
    trainer.fit()
    trainer.telemetry.flush()
    recs = [json.loads(ln) for ln in open(jsonl)]
    finals = [r for r in recs if r["event"] == "profile" and r.get("final")
              and not r.get("error")]
    assert finals, [r for r in recs if r["event"] == "profile"]
    d = finals[-1]
    assert sum(c["ms"] for c in d["classes"].values()) == pytest.approx(
        d["device_ms_per_step"], rel=0.05)
    for c in d["classes"].values():
        assert c["verdict"] in VERDICTS
    assert any("layer" in k for k in d["layers"])
    trainer.telemetry.close()
    tme.bus.reset()
