"""The seam between a configuration and a model (tpuic/models/__init__.py).

Construction only — no ``init`` — so the whole file runs in seconds: flax
modules are dataclasses, and ``==`` on two of them compares every field.
"""

import dataclasses
import warnings

import jax.numpy as jnp
import pytest

from tpuic.config import ModelConfig
from tpuic.models import (ATTENTION_IMPLS, MODEL_REMAT_POLICIES,
                          available_models, create_model,
                          create_model_from_config, family)
from tpuic.train.step import resolve_remat_policy

# Which backbone flag carries each model-level remat policy.
FLAG = {"attention": "remat_core", "blocks": "remat_blocks",
        "gelu": "remat_mlp"}
# One name per family, and what the family declares.
FAMILIES = {"resnet50": set(), "efficientnet-b0": set(),
            "inceptionv3": set(), "vit-tiny": {"attention", "blocks", "gelu"},
            "vit-tiny-moe": {"attention", "blocks", "gelu"},
            "ouro-tiny": {"blocks"}}


@pytest.mark.parametrize("name", available_models())
def test_one_constructor_for_every_registered_name(name):
    model = create_model_from_config(ModelConfig(name=name, num_classes=10))
    assert model.has_aux == family(name).has_aux == (name == "inceptionv3")
    assert create_model(name, 10) == model
    # dtypes arrive as strings or as jnp dtypes; both build the same model
    assert create_model(name, 10, dtype=jnp.bfloat16,
                        param_dtype=jnp.float32) == model


def test_families_table_names_every_declaration():
    assert set(FLAG) == set(MODEL_REMAT_POLICIES)
    for name in available_models():
        declared = set(family(name).remat_policies)
        assert declared <= set(FLAG)
        assert declared in FAMILIES.values(), name
    for name, declared in FAMILIES.items():
        assert set(family(name).remat_policies) == declared


def _no_effect_warnings(cfg):
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        assert resolve_remat_policy(cfg) is None   # it lives in the model
    return [w for w in seen if "no effect" in str(w.message)]


@pytest.mark.parametrize("policy", ["attention", "blocks", "gelu"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_backbone_carries_the_flag_iff_the_family_declares_it(name, policy):
    cfg = ModelConfig(name=name, num_classes=10, remat=True,
                      remat_policy=policy)
    backbone = create_model_from_config(cfg).backbone
    declared = policy in FAMILIES[name]
    assert getattr(backbone, FLAG[policy], False) == declared
    # every other flag stays off, and remat=False sets none
    for other in set(FLAG.values()) - {FLAG[policy]}:
        assert not getattr(backbone, other, False)
    off = create_model_from_config(dataclasses.replace(cfg, remat=False))
    assert not any(getattr(off.backbone, f, False) for f in FLAG.values())
    # the step warns exactly when nothing is rematerialized
    assert bool(_no_effect_warnings(cfg)) == (not declared)
    assert not _no_effect_warnings(dataclasses.replace(cfg, remat=False))


@pytest.mark.parametrize("attention", ATTENTION_IMPLS)
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_attention_policy_wants_a_dense_core(name, attention):
    cfg = ModelConfig(name=name, num_classes=10, remat=True,
                      remat_policy="attention", attention=attention)
    effective = "attention" in FAMILIES[name] and attention == "dense"
    assert bool(_no_effect_warnings(cfg)) == (not effective)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_dots_is_the_steps_and_applies_to_every_family(name):
    cfg = ModelConfig(name=name, num_classes=10, remat=True,
                      remat_policy="dots")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resolve_remat_policy(cfg) is not None
    backbone = create_model_from_config(cfg).backbone
    assert not any(getattr(backbone, f, False) for f in FLAG.values())


def test_unknown_policy_model_and_attention_raise():
    with pytest.raises(ValueError, match="unknown remat_policy"):
        resolve_remat_policy(ModelConfig(name="vit-tiny", remat=True,
                                         remat_policy="everything"))
    # not looked at while remat is off, as before
    assert resolve_remat_policy(ModelConfig(name="vit-tiny",
                                            remat_policy="everything")) is None
    with pytest.raises(ValueError, match="unknown model"):
        family("resnet-9000")
    with pytest.raises(ValueError, match="unknown attention impl"):
        create_model("resnet50", 10, attention="sparse")
    with pytest.raises(TypeError):
        create_model("resnet50", 10, depth=6)      # not a ModelConfig field


# A field no family reads stays unread: what the factories' ``del`` lines
# promised, now by each builder naming only the fields it uses.
UNREAD = [
    ("resnet50", {"attention": "flash"}),
    ("resnet50", {"drop_path": 0.1}),
    ("resnet50", {"remat": True, "remat_policy": "blocks"}),
    ("vit-tiny", {"fused_conv_bn": True}),
    ("vit-tiny", {"bn_momentum": 0.5, "bn_eps": 1e-3, "bn_f32_stats": False}),
    ("ouro-tiny", {"attention": "flash"}),
    ("ouro-tiny", {"drop_path": 0.1, "fused_conv_bn": True}),
    ("ouro-tiny", {"remat": True, "remat_policy": "gelu"}),
    ("efficientnet-b0", {"bn_eps": 1e-5 / 2, "bn_f32_stats": False}),
    ("efficientnet-b0", {"fused_conv_bn": True, "attention": "flash"}),
    ("inceptionv3", {"bn_eps": 1e-5 / 2, "fused_conv_bn": True}),
    ("inceptionv3", {"drop_path": 0.1, "attention": "ring"}),
]


@pytest.mark.parametrize("name,fields", UNREAD,
                         ids=[f"{n}-{'-'.join(f)}" for n, f in UNREAD])
def test_a_field_the_family_does_not_read_changes_nothing(name, fields):
    assert create_model(name, 10, **fields) == create_model(name, 10)


# ... and each field a family does read reaches its backbone.
READ = [
    ("resnet50", {"bn_eps": 1e-3}), ("resnet50", {"bn_momentum": 0.5}),
    ("resnet50", {"bn_f32_stats": False}), ("resnet50", {"fused_conv_bn": True}),
    ("efficientnet-b0", {"bn_momentum": 0.5}),
    ("inceptionv3", {"bn_momentum": 0.5}),
    ("vit-tiny", {"attention": "flash"}), ("vit-tiny", {"drop_path": 0.1}),
    ("ouro-tiny", {"param_dtype": "bfloat16"}),
]


@pytest.mark.parametrize("name,fields", READ,
                         ids=[f"{n}-{'-'.join(f)}" for n, f in READ])
def test_a_field_the_family_reads_reaches_the_backbone(name, fields):
    assert (create_model(name, 10, **fields).backbone
            != create_model(name, 10).backbone)


def test_mesh_reaches_the_families_that_shard_and_no_other():
    mesh = object()     # construction only: never entered
    assert create_model("vit-tiny", 10, mesh=mesh).backbone.mesh is mesh
    assert create_model("resnet50", 10, mesh=mesh) == create_model(
        "resnet50", 10)
