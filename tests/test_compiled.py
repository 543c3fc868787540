"""tpuic.compiled: the process-wide compiled-program registry.

Contracts under test (docs/performance.md, "Compiled-program registry"):
keying discriminates everything that changes a compiled program (avals,
mesh, dtype, generation) and nothing else; generation-scoped GC retires
exactly a generation's entries; the prewarm manifest round-trips
atomically and REFUSES corruption; a registry hit performs zero backend
compiles and zero device syncs; donation_allowed is the one
authoritative cpu+cache+guard rule; and the serve engine + trainer both
actually route their executables through the registry.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.compiled import (ManifestError, ProgramKey, ProgramRegistry,
                            avals_crc, donation_allowed, load_manifest,
                            registry, save_manifest, stable_crc, tree_avals)


def _fresh():
    """Unit tests use a private ProgramRegistry — the module singleton is
    shared with every live engine/trainer in the pytest process."""
    return ProgramRegistry()


def _build_counter(reg, tag="m", calls=None):
    calls = calls if calls is not None else []

    def build():
        calls.append(tag)
        return object()

    return build, calls


# ---------------------------------------------------------------- keying

def test_key_discriminates_program_identity():
    base = dict(model="m", shapes=((4, 8, 8, 3), "aa"), mesh=(("data", 8),),
                dtype="fp32", generation=0)
    k = ProgramKey(**base)
    assert k == ProgramKey(**base)
    assert hash(k) == hash(ProgramKey(**base))
    for field, other in (("model", "m2"),
                         ("shapes", ((8, 8, 8, 3), "aa")),
                         ("shapes", ((4, 8, 8, 3), "bb")),
                         ("mesh", ()),
                         ("mesh", (("data", 4),)),
                         ("dtype", "bf16"),
                         ("generation", 1)):
        assert k != ProgramKey(**{**base, field: other}), field


def test_key_dict_round_trip_restores_hashability():
    k = ProgramKey(model="serve:x/int8", shapes=((2, 4, 4, 3), "deadbeef"),
                   mesh=(("data", 8),), dtype="int8", generation=3)
    # JSON turns the nested tuples into lists; from_dict must re-tuplify
    # or the key is unhashable and never matches.
    d = json.loads(json.dumps(k.to_dict()))
    assert ProgramKey.from_dict(d) == k
    assert hash(ProgramKey.from_dict(d)) == hash(k)


def test_get_or_compile_hit_miss_accounting():
    reg = _fresh()
    build, calls = _build_counter(reg)
    k1 = ProgramKey(model="a", dtype="fp32")
    k2 = ProgramKey(model="a", dtype="bf16")

    e1 = reg.get_or_compile(k1, build)
    assert calls == ["m"] and e1.hit_count == 0  # the call that built it
    again = reg.get_or_compile(k1, build)
    assert again is e1 and again.hit_count == 1  # shared entry, no rebuild
    assert calls == ["m"]
    reg.get_or_compile(k2, build)  # different dtype -> distinct program
    assert calls == ["m", "m"]
    assert reg.counters()["hits"] == 1
    assert reg.counters()["misses"] == 2
    assert reg.counters()["entries"] == 2


def test_peek_is_hit_only_and_lookup_is_neutral():
    reg = _fresh()
    k = ProgramKey(model="a")
    assert reg.peek(k) is None
    exe = object()
    reg.get_or_compile(k, lambda: exe)
    h0 = reg.counters()["hits"]
    assert reg.peek(k) is exe
    assert reg.counters()["hits"] == h0 + 1
    reg.lookup(k)
    assert reg.counters()["hits"] == h0 + 1  # lookup never counts


def test_aval_signature_discriminates_shape_dtype_structure():
    a = {"w": jnp.zeros((2, 3)), "b": jnp.zeros((3,))}
    same = {"w": jnp.ones((2, 3)), "b": jnp.ones((3,))}  # values differ only
    assert tree_avals(a) == tree_avals(same)
    assert avals_crc(tree_avals(a)) == avals_crc(tree_avals(same))
    for other in ({"w": jnp.zeros((3, 2)), "b": jnp.zeros((3,))},   # shape
                  {"w": jnp.zeros((2, 3), jnp.bfloat16),
                   "b": jnp.zeros((3,))},                           # dtype
                  {"w2": jnp.zeros((2, 3)), "b": jnp.zeros((3,))}):  # path
        assert avals_crc(tree_avals(other)) != avals_crc(tree_avals(a))


def test_stable_crc_is_order_insensitive_canonical():
    assert stable_crc({"a": 1, "b": 2}) == stable_crc({"b": 2, "a": 1})
    assert stable_crc({"a": 1}) != stable_crc({"a": 2})


# ----------------------------------------------------- generation-scoped GC

def test_retire_drops_exactly_one_generation():
    reg = _fresh()
    for gen in (0, 1):
        for dt in ("fp32", "int8"):
            reg.get_or_compile(ProgramKey(model="serve:e/" + dt,
                                          dtype=dt, generation=gen),
                               lambda: object())
    reg.get_or_compile(ProgramKey(model="train:r18:step"), lambda: object())
    assert len(reg) == 5
    assert reg.retire("serve:e/", generation=0) == 2
    assert len(reg) == 3
    assert all(k.generation == 1 for k in reg.keys()
               if k.model.startswith("serve:e/"))
    # No generation filter -> the whole family.
    assert reg.retire("serve:e/") == 2
    assert [k.model for k in reg.keys()] == ["train:r18:step"]


def test_retire_prefix_does_not_swallow_longer_tags():
    # "serve:1" must not retire "serve:10" — consumers retire with a
    # trailing separator; this pins that the separator is sufficient.
    reg = _fresh()
    reg.get_or_compile(ProgramKey(model="serve:1/fp32"), lambda: object())
    reg.get_or_compile(ProgramKey(model="serve:10/fp32"), lambda: object())
    assert reg.retire("serve:1/") == 1
    assert [k.model for k in reg.keys()] == ["serve:10/fp32"]


def test_evict_single_key():
    reg = _fresh()
    k = ProgramKey(model="a")
    reg.get_or_compile(k, lambda: object())
    assert reg.evict(k) is True
    assert reg.evict(k) is False
    assert len(reg) == 0


# ------------------------------------------------------------- manifest

def test_manifest_round_trip(tmp_path):
    reg = _fresh()
    keys = [ProgramKey(model="serve:e/fp32", shapes=((4, 8, 8, 3), "u8"),
                       dtype="fp32"),
            ProgramKey(model="train:r18:step", shapes=((16, 24, 24, 3),),
                       mesh=(("data", 8),), dtype="bf16", generation=2)]
    for k in keys:
        reg.get_or_compile(k, lambda: object())
    path = str(tmp_path / "programs.manifest.json")
    assert reg.write_manifest(path) == 2
    entries = load_manifest(path)
    assert sorted((ProgramKey.from_dict(e["key"]) for e in entries),
                  key=repr) == sorted(keys, key=repr)
    assert all(e["compile_s"] >= 0 for e in entries)


def test_manifest_prefix_filter(tmp_path):
    reg = _fresh()
    reg.get_or_compile(ProgramKey(model="serve:e/fp32"), lambda: object())
    reg.get_or_compile(ProgramKey(model="train:r18:step"), lambda: object())
    path = str(tmp_path / "m.json")
    assert reg.write_manifest(path, model_prefix="train:") == 1
    [e] = load_manifest(path)
    assert e["key"]["model"] == "train:r18:step"


def test_manifest_refuses_corruption(tmp_path):
    path = str(tmp_path / "m.json")
    save_manifest(path, [{"key": ProgramKey(model="a").to_dict(),
                          "compile_s": 0.5}])
    load_manifest(path)  # sanity: intact file loads
    raw = open(path).read()
    # Flip a payload byte under an unchanged CRC -> refusal.
    torn = raw.replace('"model": "a"', '"model": "b"')
    assert torn != raw
    with open(path, "w") as f:
        f.write(torn)
    with pytest.raises(ManifestError, match="CRC"):
        load_manifest(path)
    # Unknown version -> refusal.
    doc = json.loads(raw)
    doc["version"] = 99
    with open(path, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ManifestError, match="version"):
        load_manifest(path)
    # Not JSON at all -> refusal (never a crash mid-prewarm).
    with open(path, "w") as f:
        f.write("{half a manifes")
    with pytest.raises(ManifestError, match="JSON"):
        load_manifest(path)
    # Absent file is a first boot, not an integrity failure.
    with pytest.raises(FileNotFoundError):
        load_manifest(str(tmp_path / "nope.json"))


def test_manifest_write_is_atomic_no_tmp_litter(tmp_path):
    path = str(tmp_path / "m.json")
    save_manifest(path, [])
    save_manifest(path, [{"key": ProgramKey(model="a").to_dict(),
                          "compile_s": 0.0}])  # overwrite in place
    assert [p.name for p in tmp_path.iterdir()] == ["m.json"]


# ------------------------------------------------- steady-state contracts

def test_registry_hit_is_zero_compile_zero_sync():
    from tpuic.analysis.runtime import assert_compiles_flat, count_device_gets
    reg = _fresh()
    x = jnp.arange(8, dtype=jnp.float32)
    fn = jax.jit(lambda v: v * 2.0)
    k = ProgramKey(model="unit:double", shapes=((8,), "f32"))
    e = reg.get_or_compile(
        k, lambda: fn.lower(x).compile())
    jax.block_until_ready(e.executable(x))  # warm
    with assert_compiles_flat(0, what="registry hit path"), \
            count_device_gets() as gets:
        exe = reg.peek(k)
        assert exe is not None
        out = exe(x)
    assert gets.count == 0
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 2.0)


def test_donation_allowed_truth_table():
    # Guard off -> always allowed, no matter the backend/cache.
    assert donation_allowed(guard_active=False) is True
    # This suite runs guard+cache+cpu (conftest configures the persistent
    # cache; JAX_PLATFORMS=cpu): the one lethal combination.
    assert jax.default_backend() == "cpu"
    cache_dir = jax.config.jax_compilation_cache_dir
    assert cache_dir
    assert donation_allowed(guard_active=True) is False
    # Drop the cache -> allowed again (two of three conditions are fine).
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert donation_allowed(guard_active=True) is True
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)


# ------------------------------------------------ persistent-cache helper

@pytest.mark.parametrize("env,flag,want", [
    ("/x/from-env", "", "/x/from-env"),            # the environment wins
    ("/x/from-env", "/y/from-flag", "/x/from-env"),  # over the serve flag too
    ("", "", None),                                # the fixed in-checkout path
], ids=["env_set", "env_beats_flag", "unset"])
def test_enable_compile_cache_location(monkeypatch, env, flag, want):
    """tpuic/compiled/cache.py is the only code that names the cache
    directory: with JAX_COMPILATION_CACHE_DIR set it leaves the directory
    to JAX (no ``jax_compilation_cache_dir`` update at all); unset, it is
    one fixed path inside the checkout."""
    from tpuic.compiled import cache
    if env:
        monkeypatch.setenv(cache.CACHE_ENV, env)
    else:
        monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    got = cache.enable_compile_cache(flag)
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 1.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == 0
    if want is not None:
        assert got == want
        assert "jax_compilation_cache_dir" not in updates
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, "tests", ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == got


# --------------------------------------------------- consumer integration

def _sum_forward(variables, images):
    s = jnp.sum(images.astype(jnp.float32), axis=(1, 2, 3))
    return s + variables["bias"]


def test_engine_routes_through_registry_and_retires_on_swap():
    from tpuic.serve import InferenceEngine
    eng = InferenceEngine(forward_fn=_sum_forward,
                          variables={"bias": jnp.float32(0.0)},
                          image_size=4, buckets=(1, 2), cache_tag="t-swap")
    try:
        eng.warmup()
        mine = [k for k in registry.keys()
                if k.model.startswith("t-swap/")]
        assert len(mine) == 2 and all(k.generation == 0 for k in mine)
        # Aval-identical swap: same keys recompute -> executables reused,
        # nothing retired, nothing recompiled.
        s = eng.swap_weights({"bias": jnp.float32(1.0)})
        assert s["reused_executables"] is True
        assert sorted(map(repr, mine)) == sorted(
            repr(k) for k in registry.keys()
            if k.model.startswith("t-swap/"))
        # Aval-changing swap: new program generation compiles, the old
        # generation's entries are GCed after the flip.
        s = eng.swap_weights({"bias": jnp.zeros((1,), jnp.float32)})
        assert s["reused_executables"] is False
        after = [k for k in registry.keys() if k.model.startswith("t-swap/")]
        assert len(after) == 2 and all(k.generation == 1 for k in after)
    finally:
        eng.close()
        registry.retire("t-swap/")


def test_engine_prewarm_from_manifest_is_steady_state(tmp_path):
    from tpuic.analysis.runtime import assert_compiles_flat
    from tpuic.serve import InferenceEngine
    manifest = str(tmp_path / "programs.manifest.json")

    def eng():
        return InferenceEngine(forward_fn=_sum_forward,
                               variables={"bias": jnp.float32(0.0)},
                               image_size=4, buckets=(1, 2),
                               cache_tag="t-prewarm")

    a = eng()
    try:
        a.warmup()
        registry.write_manifest(manifest, model_prefix="t-prewarm/")
    finally:
        a.close()
    registry.retire("t-prewarm/")  # simulate the dead process

    b = eng()
    try:
        assert b.prewarm(manifest) == 2
        assert registry.counters()["prewarmed"] >= 2
        rng = np.random.default_rng(0)
        with assert_compiles_flat(0, what="manifest-prewarmed traffic"):
            futs = [b.submit(rng.standard_normal((n, 4, 4, 3))
                             .astype(np.float32)) for n in (1, 2, 1)]
            for f in futs:
                f.result(timeout=60)
    finally:
        b.close()
        registry.retire("t-prewarm/")


@pytest.mark.slow
def test_trainer_steps_live_in_registry(imagefolder, tmp_path):
    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.train.loop import Trainer
    cfg = Config(
        data=DataConfig(data_dir=imagefolder, resize_size=32, batch_size=2,
                        num_workers=0, shuffle_seed=0),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="sgd", learning_rate=0.01,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=1, ckpt_dir=str(tmp_path / "cp"),
                      save_period=1),
        mesh=MeshConfig(),
    )
    Trainer(cfg, log_dir=str(tmp_path / "logs"))
    mine = [k for k in registry.keys() if k.model.startswith("train:")]
    try:
        assert {k.model for k in mine} >= {"train:resnet18-cifar:step",
                                           "train:resnet18-cifar:eval"}
    finally:
        registry.retire("train:")
