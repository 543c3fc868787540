"""The latent-attention, routed-expert configuration: its file against the
catalog row it was copied from, the rule for its cut, its plain reference
against the real train step at the tiny size on the CPU, what each of the
model's own faults reads there, and the two readers of its counters."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, train_check
from benchmark.cuts import check_config_cut
from benchmark.reference import kanana, steps

SPEC = harness.load_spec()
ENTRY = next(c for c in SPEC["configs"] if c["name"] == "kanana2_30b_a3b")
CELL = next(w["name"] for w in SPEC["workloads"]
            if w["config"] == ENTRY["name"])
with open(os.path.join(harness.REPO, ENTRY["file"])) as f:
    BODY = json.load(f)
TINY = harness.resolve_cell(SPEC, CELL, tiny=True)
CONFIG, TRAFFIC = TINY["config"], TINY["traffic"]
MODEL = CONFIG["train_flags"][CONFIG["train_flags"].index("--model") + 1]

# the `config` of the row kanana-2-30b-a3b-instruct-2601 in the catalog
# beside the model-configs guide (architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256,
}
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
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
        "num_hidden_layers", "n_routed_experts"]
    assert (BODY["num_hidden_layers"], BODY["n_routed_experts"]) == (6, 8)
    assert BODY["leading_dense_layers"] == BODY["first_k_dense_replace"] == 1
    assert BODY["layer_period"] == BODY["moe_layer_freq"] == 1
    assert "16 chips" in BODY["deployment"] and "rank 0" in BODY["deployment"]
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


def test_the_cut_passes_the_rule():
    assert check_config_cut(ENTRY, BODY) == []


@pytest.mark.parametrize("key,here", [("moe_intermediate_size", 384),
                                      ("num_experts_per_tok", 3),
                                      ("kv_lora_rank", 256)])
def test_a_cut_width_is_refused(key, here):
    reduced = BODY["reduced"] + [key]
    body = {**BODY, key: here, "reduced": reduced,
            "published": {**BODY["published"], key: BODY[key]}}
    wrong = check_config_cut({**ENTRY, "reduced": reduced}, body)
    assert len(wrong) == 1 and key in wrong[0] and "never cut" in wrong[0]


@pytest.mark.parametrize("key,here,what", [
    ("n_routed_experts", 4, "floor is 8"),
    ("num_hidden_layers", 4, "leave 3 after")])
def test_a_cut_under_the_floors_is_refused(key, here, what):
    wrong = check_config_cut(ENTRY, {**BODY, key: here})
    assert len(wrong) == 1 and what in wrong[0]


# -- the reference against the real step ------------------------------------

def _model(dtype="float32"):
    from tpuic.models import create_model
    return create_model(MODEL, CONFIG["num_classes"], dtype=dtype)


def _variables(seed=1):
    v = harness.plain_variables(_model().init(
        jax.random.key(seed), jnp.zeros((1, 32, 32, 3)), train=False))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * rng.standard_normal(a.shape).astype(np.float32),
        v)


def _batches(n, rows=8, seed=0):
    rng = np.random.default_rng(seed)
    return [{"image": rng.standard_normal((rows, 32, 32, 3)).astype(
        np.float32), "label": rng.integers(0, CONFIG["num_classes"], rows
                                           ).astype(np.int32),
             "mask": np.ones(rows, np.float32)} for _ in range(n)]


def test_the_reference_imports_nothing_of_the_program():
    with open(kanana.__file__) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert names and all(n.split(".")[0] in ("__future__", "jax", "numpy",
                                             "benchmark") for n in names)
    # and reads every size from the configuration: no width is written out
    with open(kanana.__file__) as f:
        code = f.read().split('"""', 2)[2]
    assert not [w for w in ("2048", "6144", "768", "512", "192", "2.448")
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
    tx = make_optimizer(cfg.optim, 8, 1, global_batch=8)
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
    assert float(metrics["routed_pairs"]) == 8 * 64 * 3
    got = train_check.program_readings(first, TRAFFIC["optimizer"])
    want = steps.follow(kanana, v, batches, CONFIG, TRAFFIC["optimizer"])
    values, where = train_check.numbers(got, want)
    assert values["loss_gap"] < 1e-5, values
    assert values["grad_gap"] < 1e-3, (values, where)
    assert values["change_gap"] < 5e-3, (values, where)
    limits = train_check.limits(CONFIG)
    assert len(limits) == 3
    assert all(values[name] < limit / 10 for name, limit in limits.items())


FAULTS = {
    "one_expert_a_token_fewer": {"num_experts_per_tok": 2},
    "weights_not_renormalised": {"norm_topk_prob": False},
    "selection_bias_left_out": {"topk_method": "greedy"},
    "routed_scale_of_one": {"routed_scaling_factor": 1},
    "rotary_on_the_unrotated_part": {"rotary_on": "nope"},
    "shared_experts_left_out": {"n_shared_experts": 0},
}


@pytest.fixture(scope="module")
def sound():
    with jax.default_matmul_precision("highest"):
        v, batches = _variables(), _batches(3)
        return v, batches, steps.follow(kanana, v, batches, CONFIG,
                                        TRAFFIC["optimizer"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_of_the_models_own_reads_over_a_limit(fault, sound):
    """Planted in the reference put in the program's place, as
    ``benchmark/fault_readings.py --set`` plants them on the chip."""
    v, batches, want = sound
    broken = {**CONFIG, **FAULTS[fault]}
    values, _ = train_check.numbers(
        steps.follow(kanana, v, batches, broken, TRAFFIC["optimizer"]), want)
    x = batches[0]["image"]
    values["forward_gap"] = harness.centred_error(
        kanana.forward(v, x, broken), kanana.forward(v, x, CONFIG))
    limits = {**train_check.limits(CONFIG),
              "forward_gap": CONFIG["reference_tolerance"]}
    over = {n for n, limit in limits.items() if values[n] > limit}
    assert over, (values, limits)


# -- the readers ------------------------------------------------------------

def _obs(**spans):
    return harness.Observations(step_events=[], engine_stats={}, trace=None,
                                spans={"epoch_gap_ms": [], **spans})


def test_the_readers_read_the_windows_epochs_and_nothing_of_a_program_without(
        ledger, fill_ledger):
    load = harness.load_reader("expert_load_max_over_mean")
    share = harness.load_reader("routed_pairs_held_share")
    assert load(_obs()) is None and share(_obs()) is None    # no epoch yet
    fill_ledger()
    # a program whose spans lack the counters (the parent commit's)
    assert load(_obs()) is None and share(_obs()) is None
    epochs = sorted((r for r in ledger.snapshot()
                     if r["name"] == "train_epoch"), key=lambda r: r["t0"])
    for i, r in enumerate(epochs):
        r["attrs"].update(routed_pairs=37632.0,
                          routed_pairs_held=2352.0 + 100 * i,
                          expert_load_max_over_mean=[9.0, 1.2, 1.3, 1.5,
                                                     1.1][i])
    # the warm-up epoch is left out; median over the other four
    assert load(_obs()) == pytest.approx(1.25)
    assert share(_obs()) == pytest.approx(100 * 2602.0 / 37632.0)
    assert load(harness.Observations(               # not a training run
        step_events=[], engine_stats={}, trace=None, spans={})) is None
    del epochs[2]["attrs"]["routed_pairs_held"]
    assert share(_obs()) is None
