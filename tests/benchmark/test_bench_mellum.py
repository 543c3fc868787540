"""The sliding-window, grouped-head, softmax-routed configuration: its file
against the catalog row it was copied from, the rule for its cut, its plain
reference against the real train step at the tiny size on the CPU, what
each of the model's own faults reads there, and the readers of its
counters and of its kernels' device time."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, train_check
from benchmark.cuts import check_config_cut
from benchmark.layer_metrics import _banded
from benchmark.reference import mellum, steps

SPEC = harness.load_spec()
ENTRY = next(c for c in SPEC["configs"] if c["name"] == "mellum2_12b_a2.5b")
CELL = next(w["name"] for w in SPEC["workloads"]
            if w["config"] == ENTRY["name"])
with open(os.path.join(harness.REPO, ENTRY["file"])) as f:
    BODY = json.load(f)
TINY = harness.resolve_cell(SPEC, CELL, tiny=True)
CONFIG, TRAFFIC = TINY["config"], TINY["traffic"]
MODEL = CONFIG["train_flags"][CONFIG["train_flags"].index("--model") + 1]
SIZE = CONFIG["image_size"]

# the `config` of the row Mellum2-12B-A2.5B-Instruct in the catalog beside
# the model-configs guide (architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the file ---------------------------------------------------------------

@pytest.mark.parametrize("key", sorted(CATALOG))
def test_the_file_holds_the_catalog_rows_value(key):
    """Key by key: only the two counts in ``reduced`` differ, and for those
    ``published`` holds the catalog's value."""
    if key in BODY["reduced"]:
        assert BODY["published"][key] == CATALOG[key] > BODY[key]
    else:
        assert key in BODY and BODY[key] == CATALOG[key]
        assert type(BODY[key]) is type(CATALOG[key])


def test_the_entry_and_the_file_agree_and_name_the_source():
    assert ENTRY["source"] == BODY["source"] == SOURCE
    assert ENTRY["reduced"] == BODY["reduced"] == [
        "num_hidden_layers", "num_experts"]
    assert (BODY["num_hidden_layers"], BODY["num_experts"]) == (4, 8)
    assert BODY["leading_dense_layers"] == 0 and BODY["layer_period"] == 4
    # the layers held are one whole period of the pattern
    assert BODY["layer_types"][:4] == BODY["layer_types"][4:8]
    assert "8 chips" in BODY["deployment"] and "rank 0" in BODY["deployment"]
    assert "7 such groups" in BODY["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the sandbox's copy, where there is one
        with open(catalog) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        row = next(r for r in rows if r["source_url"] == SOURCE)
        assert row["config"] == CATALOG
    limits = train_check.limits(BODY)
    assert set(limits) == {"loss_gap", "grad_gap_median",
                           "change_gap_median"}
    for key in ("reference_tolerance", *train_check.NUMBERS.values()):
        if key in BODY:
            assert len(BODY[key + "_why"]) > 100, key
    assert (BODY["image_size"], BODY["patch"], BODY["tokens"]) == (
        1024, 16, 4096)
    for fault in ("sliding_window", "full_attention_rope", "kv_head_of",
                  "num_experts_per_tok", "norm_topk_prob"):
        assert fault in BODY            # fault_readings.py --set KEY=VALUE


def test_the_cut_passes_the_rule():
    assert check_config_cut(ENTRY, BODY) == []


@pytest.mark.parametrize("key,here", [("moe_intermediate_size", 448),
                                      ("num_experts_per_tok", 4),
                                      ("sliding_window", 512),
                                      ("head_dim", 64)])
def test_a_cut_width_is_refused(key, here):
    reduced = BODY["reduced"] + [key]
    body = {**BODY, key: here, "reduced": reduced,
            "published": {**BODY["published"], key: BODY[key]}}
    wrong = check_config_cut({**ENTRY, "reduced": reduced}, body)
    assert len(wrong) == 1 and key in wrong[0] and "never cut" in wrong[0]


@pytest.mark.parametrize("key,here,what", [
    ("num_experts", 4, "floor is 8"),
    ("num_hidden_layers", 3, "a whole period of the pattern (4)")])
def test_a_cut_under_the_floors_is_refused(key, here, what):
    wrong = check_config_cut(ENTRY, {**BODY, key: here})
    assert len(wrong) == 1 and what in wrong[0]


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = harness.resolve_cell(SPEC, CELL)["traffic"]
    assert (mix["mode"], mix["loader"], mix["per_chip_batch"],
            mix["train_images"], mix["val_images"], mix["classes"],
            mix["unique_per_class"], mix["corpus_seed"], mix["trace_steps"],
            mix["trace_max_s"]) == ("train", "resident", 4, 128, 16, 8, 16,
                                    20260926, 12, 4.0)
    assert mix["optimizer"] == {"name": "adam", "learning_rate": 5e-06,
                                "b1": 0.9, "b2": 0.999, "eps": 1e-08}
    flags = mix["train_flags"]
    assert flags[flags.index("--log-every-steps") + 1] == "4"
    assert flags[-1] == "--milestones" and "--batchsize" in flags
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"].endswith("b4_px1024")
    mine = {m["name"] for m in SPEC["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"attention_key_blocks_visited_share",
            "banded_attention_roofline_share", "step_transient_gib",
            "expert_load_max_over_mean", "routed_pairs_held_share",
            "device_mfu", "device_step_ms"} <= mine


# -- the reference against the real step ------------------------------------

def _model(dtype="float32"):
    from tpuic.models import create_model
    return create_model(MODEL, CONFIG["num_classes"], dtype=dtype)


def _variables(seed=1):
    v = harness.plain_variables(_model().init(
        jax.random.key(seed), jnp.zeros((1, SIZE, SIZE, 3)), train=False))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * rng.standard_normal(a.shape).astype(np.float32),
        v)


def _batches(n, rows=4, seed=0):
    rng = np.random.default_rng(seed)
    return [{"image": rng.standard_normal((rows, SIZE, SIZE, 3)).astype(
        np.float32), "label": rng.integers(0, CONFIG["num_classes"], rows
                                           ).astype(np.int32),
             "mask": np.ones(rows, np.float32)} for _ in range(n)]


def test_the_reference_imports_nothing_of_the_program():
    with open(mellum.__file__) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert names and all(n.split(".")[0] in ("__future__", "jax", "numpy",
                                             "benchmark") for n in names)
    # and reads every size from the configuration: no width is written out
    with open(mellum.__file__) as f:
        code = f.read().split('"""', 2)[2]
    assert not [w for w in ("2304", "4096", "1024", "896", "500000", "1.277")
                if w in code]


def test_three_steps_of_the_real_train_step_follow_the_reference():
    import train
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import TrainState
    from tpuic.train.step import make_train_step
    v, batches = _variables(), _batches(3)
    args = train.build_parser().parse_args(
        [*CONFIG["train_flags"], *TRAFFIC["train_flags"], "--datadir", "x",
         "--dtype", "float32"])
    cfg = train.config_from_args(args)
    model = _model()
    tx = make_optimizer(cfg.optim, 8, 1, global_batch=4)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=v["params"],
                       batch_stats={}, opt_state=tx.init(v["params"]),
                       apply_fn=model.apply, tx=tx, ema_params=None,
                       skip_count=jnp.zeros((), jnp.int32))

    class Holder:
        train_step = staticmethod(make_train_step(cfg.optim, cfg.model,
                                                  mesh=None, donate=False))
        state = None
    first = train_check.FirstSteps(Holder, len(batches))
    metrics = None
    for batch in batches:
        state, metrics = Holder.train_step(
            state, {k: jnp.asarray(b) for k, b in batch.items()})
    assert first.done
    assert float(metrics["routed_pairs_dropped"]) == 0.0
    assert float(metrics["routed_pairs"]) == 4 * 256 * 3
    assert float(metrics["attention_key_blocks_visited"]) == 31.0
    got = train_check.program_readings(first, TRAFFIC["optimizer"])
    want = steps.follow(mellum, v, batches, CONFIG, TRAFFIC["optimizer"])
    values, where = train_check.numbers(got, want)
    assert values["loss_gap"] < 1e-5, values
    assert values["grad_gap"] < 1e-3, (values, where)
    assert values["change_gap"] < 5e-3, (values, where)
    limits = train_check.limits(CONFIG)
    assert len(limits) == 3
    assert all(values[name] < limit / 10 for name, limit in limits.items())


FAULTS = {
    "the_window_ignored": {"sliding_window": 4096},
    "yarn_left_out": {"full_attention_rope": "default"},
    "key_value_heads_dealt_out_in_turn": {"kv_head_of": "modulo"},
    "one_expert_a_token_fewer": {"num_experts_per_tok": 2},
    "weights_not_renormalised": {"norm_topk_prob": False},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_of_the_models_own_moves_the_forward_over_its_limit(fault):
    """Planted in the reference put in the program's place, as
    ``benchmark/fault_readings.py --set`` plants them on the chip."""
    v, x = _variables(), _batches(1)[0]["image"]
    gap = harness.centred_error(
        mellum.forward(v, x, {**CONFIG, **FAULTS[fault]}),
        mellum.forward(v, x, CONFIG))
    assert gap > CONFIG["reference_tolerance"], gap


# -- the readers ------------------------------------------------------------

def _obs(trace=None, **spans):
    return harness.Observations(step_events=[], engine_stats={}, trace=trace,
                                spans={"epoch_gap_ms": [], **spans})


def test_the_share_reader_reads_the_windows_epochs_and_nothing_of_a_program_without(
        ledger, fill_ledger):
    share = harness.load_reader("attention_key_blocks_visited_share")
    assert share(_obs()) is None                    # no epoch yet
    fill_ledger()
    assert share(_obs()) is None    # spans without the counters (the parent)
    epochs = sorted((r for r in ledger.snapshot()
                     if r["name"] == "train_epoch"), key=lambda r: r["t0"])
    for r in epochs:
        r["attrs"].update(attention_key_blocks_visited=99.0,
                          attention_key_blocks_square=256.0)
    assert share(_obs()) == pytest.approx(100 * 99 / 256)
    del epochs[2]["attrs"]["attention_key_blocks_square"]
    assert share(_obs()) is None


def test_the_roofline_reader_takes_the_kernels_time_from_the_runs_own_trace(
        monkeypatch, tmp_path):
    """A trace made by hand: two kernels' ops of 30 + 10 ms in each of the
    steps of a window, beside other ops; the required work is the
    configuration's, by hand 3.81 TFLOP a step of 4 images."""
    from benchmark import trace_reduce
    roofline = harness.load_reader("banded_attention_roofline_share")
    reduced = {"devices": {0: {"steps": 3}}, "busy_s": 1.0, "window_s": 1.0}
    assert roofline(_obs()) is None                     # untraced
    assert roofline(_obs({"devices": {}})) is None      # a CPU's trace
    work = tmp_path / "work"
    where = work / CELL / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path))
    modules = [("jit_train_step(1)", 1.0 * i, 0.9) for i in range(6)]
    ops = []
    for i in range(6):
        ops += [("%banded_attention_fwd.3 = bf16[4] custom-call()",
                 1.0 * i + 0.1, 0.030),
                ("%fusion.7 = bf16[4] fusion()", 1.0 * i + 0.2, 0.5),
                ("%banded_attention_dkv.1 = bf16[4] custom-call()",
                 1.0 * i + 0.8, 0.010)]
    raw = {"devices": {0: {"modules": modules, "ops": ops, "async_ops": []}},
           "annotations": []}
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: raw)
    full = harness.resolve_cell(SPEC, CELL)["config"]
    flops = _banded.core_flops_per_step(full, 4)
    assert abs(flops / 3.8146e12 - 1) < 1e-3
    got = roofline(_obs(reduced, device_kind="TPU v5 lite"))
    assert got == pytest.approx(100 * flops / (0.040 * 197e12))
    # a program without the kernel (the parent commit): nothing to read
    raw["devices"][0]["ops"] = [o for o in ops if "banded" not in o[0]]
    assert roofline(_obs(reduced, device_kind="TPU v5 lite")) is None
    # a cell that is not the benchmark's (a candidate's trace)
    os.rename(work / CELL, work / "some_other_cell")
    assert roofline(_obs(reduced, device_kind="TPU v5 lite")) is None
