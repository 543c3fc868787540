"""Model registry.

``create_model(name, num_classes)`` is the framework equivalent of
``Classifier(name, num_classes)`` in reference nn/classifier.py:8-34. Accepted
names cover the reference's selector strings ('resnet50', 'resnet101',
'inceptionv3', 'efficientnet-b3' — nn/classifier.py:11-23) plus the
BASELINE.md parity-config additions ('resnet18', 'efficientnet-b0',
'vit-b16').
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet

import jax.numpy as jnp
from flax import linen as nn

from tpuic.config import ModelConfig
from tpuic.models.classifier import Classifier
from tpuic.models import resnet as _resnet
from tpuic.models import efficientnet as _effnet
from tpuic.models import inception as _inception
from tpuic.models import vit as _vit
from tpuic.models import ouro as _ouro
from tpuic.models import kanana as _kanana
from tpuic.models import mellum as _mellum


# The ``ModelConfig.remat_policy`` values that are flags of a backbone;
# 'dots' is the train step's own and applies to every family.
MODEL_REMAT_POLICIES = ("attention", "blocks", "gelu")


@dataclasses.dataclass(frozen=True)
class Family:
    """What a registered name is: how to build its backbone, and what the
    rest of the program may ask about it without knowing its name."""

    # Reads the ModelConfig fields its family uses, and no others.
    build: Callable[[ModelConfig, Any], nn.Module]
    has_aux: bool = False
    # Which of MODEL_REMAT_POLICIES the backbone implements.
    remat_policies: FrozenSet[str] = frozenset()


_REGISTRY: Dict[str, Family] = {}


def register(name: str, build: Callable[[ModelConfig, Any], nn.Module],
             has_aux: bool = False, remat_policies=()):
    _REGISTRY[name] = Family(build, has_aux, frozenset(remat_policies))


def available_models():
    return sorted(_REGISTRY)


def family(name: str) -> Family:
    if name not in _REGISTRY:
        raise ValueError(f"unknown model '{name}'; available: {available_models()}")
    return _REGISTRY[name]


# Single source of truth: the module whose attention dispatch consumes it.
from tpuic.models.vit import ATTENTION_IMPLS  # noqa: E402,F401


def create_model_from_config(cfg: ModelConfig, mesh=None) -> Classifier:
    fam = family(cfg.name)
    if cfg.attention not in ATTENTION_IMPLS:
        raise ValueError(f"unknown attention impl '{cfg.attention}'; "
                         f"available: {ATTENTION_IMPLS}")
    return Classifier(backbone=fam.build(cfg, mesh),
                      num_classes=cfg.num_classes,
                      head_widths=tuple(cfg.head_widths),
                      has_aux=fam.has_aux, **_dtypes(cfg))


def create_model(name: str, num_classes: int, *, mesh=None,
                 **fields) -> Classifier:
    """``create_model_from_config`` on ``ModelConfig(name, num_classes,
    **fields)``."""
    return create_model_from_config(
        ModelConfig(name=name, num_classes=num_classes, **fields), mesh)


def _dtypes(cfg: ModelConfig) -> dict:
    return {"dtype": jnp.dtype(cfg.dtype),
            "param_dtype": jnp.dtype(cfg.param_dtype)}


def _remat(cfg: ModelConfig, policy: str) -> bool:
    # A model-level policy (ModelConfig.remat_policy) is a flag of the
    # backbone, not a step-level jax.checkpoint (resolve_remat_policy).
    return cfg.remat and cfg.remat_policy == policy


def _register_builtins():
    def _rn(name, ctor, **extra):
        def build(cfg, mesh):
            # fused_conv_bn: inference-only Pallas fused conv+BN+ReLU
            # (kernels/conv_bn_relu.py), this family's alone.
            return ctor(**_dtypes(cfg), bn_momentum=cfg.bn_momentum,
                        bn_eps=cfg.bn_eps, bn_f32_stats=cfg.bn_f32_stats,
                        fused_inference=cfg.fused_conv_bn, **extra)
        register(name, build)

    _rn("resnet18", _resnet.resnet18)
    _rn("resnet34", _resnet.resnet34)
    _rn("resnet50", _resnet.resnet50)
    _rn("resnet101", _resnet.resnet101)
    _rn("resnet152", _resnet.resnet152)
    _rn("resnet18-cifar", _resnet.resnet18, small_stem=True)
    # MLPerf-style space-to-depth stem: identical math to resnet50 (the
    # 7x7/s2 stem re-indexed as 4x4/s1 on [H/2,W/2,12]), better MXU layout;
    # convert standard stem weights with models.resnet.s2d_stem_kernel.
    _rn("resnet50-s2d", _resnet.resnet50, space_to_depth=True)

    def _eff(name, variant):
        def build(cfg, mesh):
            # torch effnet: eps 1e-3 (module default, not cfg.bn_eps); f32
            # stats kept (the experiment is ResNet-scoped,
            # ModelConfig.bn_f32_stats).
            return _effnet.efficientnet(variant, **_dtypes(cfg),
                                        bn_momentum=cfg.bn_momentum)
        register(name, build)

    for v in ("b0", "b1", "b2", "b3", "b4", "b5", "b6", "b7"):
        _eff(f"efficientnet-{v}", v)

    def _vit_family(name, ctor):
        def build(cfg, mesh):
            return ctor(**_dtypes(cfg), attention=cfg.attention, mesh=mesh,
                        drop_path=cfg.drop_path,
                        remat_core=_remat(cfg, "attention"),
                        remat_blocks=_remat(cfg, "blocks"),
                        remat_mlp=_remat(cfg, "gelu"))
        register(name, build, remat_policies=MODEL_REMAT_POLICIES)

    _vit_family("vit-b16", _vit.vit_b16)
    _vit_family("vit-l16", _vit.vit_l16)
    _vit_family("vit-b32", _vit.vit_b32)
    _vit_family("vit-l32", _vit.vit_l32)
    _vit_family("vit-s16", _vit.vit_s16)
    _vit_family("vit-tiny", _vit.vit_tiny)
    # Switch-MoE variants (models/moe.py): expert-parallel over the mesh
    # 'model' axis; beyond-parity (reference is dense-only, SURVEY.md §2c).
    _vit_family("vit-s16-moe", _vit.vit_s16_moe)
    _vit_family("vit-tiny-moe", _vit.vit_tiny_moe)

    def _looped(name, ctor, **extra):
        def build(cfg, mesh):
            # RMSNorm only; the causal rotary core is dense (196 tokens),
            # whatever cfg.attention says.
            return ctor(**_dtypes(cfg),
                        remat_blocks=_remat(cfg, "blocks"), **extra)
        register(name, build, remat_policies=("blocks",))

    # Looped decoder stack as a backbone (models/ouro.py): Ouro-2.6B at its
    # published depth, and the first pipeline stage of eight (6 of the 48
    # layers; every width and the four passes as published).
    _looped("ouro-2.6b", _ouro.ouro_2_6b)
    _looped("ouro-2.6b-l6", _ouro.ouro_2_6b, depth=6)
    _looped("ouro-tiny", _ouro.ouro_tiny)

    def _latent_moe(name, ctor, **extra):
        def build(cfg, mesh):
            # as the looped stack: RMSNorm only, a dense causal core
            return ctor(**_dtypes(cfg),
                        remat_blocks=_remat(cfg, "blocks"), **extra)
        register(name, build, remat_policies=("blocks",))

    # Latent attention and routed experts (models/kanana.py):
    # Kanana-2-30B-A3B at its published counts, and expert-parallel rank 0
    # of 16 in the first pipeline stage of eight (6 of the 48 layers, 8 of
    # the 128 experts of each; every width and the router as published).
    _latent_moe("kanana-2-30b-a3b", _kanana.kanana_2_30b_a3b)
    _latent_moe("kanana-2-30b-a3b-l6e8", _kanana.kanana_2_30b_a3b, depth=6,
                held=(0, 8))
    _latent_moe("kanana-tiny", _kanana.kanana_tiny)

    def _band_moe(name, ctor, **extra):
        def build(cfg, mesh):
            # RMSNorm only; the core is the banded flash kernel (causal,
            # windowed, grouped heads), whatever cfg.attention says
            return ctor(**_dtypes(cfg), mesh=mesh,
                        remat_blocks=_remat(cfg, "blocks"), **extra)
        register(name, build, remat_policies=("blocks",))

    # Sliding-window and full layers, grouped key-value heads, a softmax
    # router (models/mellum.py): Mellum2-12B-A2.5B at its published counts,
    # and expert-parallel rank 0 of 8 in the first pipeline stage of seven
    # (one period of 4 of the 28 layers, 8 of the 64 experts of each;
    # every width, the window, the tables and the router as published).
    _band_moe("mellum2-12b-a2.5b", _mellum.mellum2_12b_a2_5b)
    _band_moe("mellum2-12b-a2.5b-l4e8", _mellum.mellum2_12b_a2_5b, depth=4,
              held=(0, 8))
    _band_moe("mellum-tiny", _mellum.mellum_tiny)

    def _inc(cfg, mesh):
        # torch inception: eps 1e-3 (module default, not cfg.bn_eps); f32
        # stats kept.
        return _inception.InceptionV3(aux_classes=cfg.num_classes,
                                      **_dtypes(cfg),
                                      bn_momentum=cfg.bn_momentum)

    register("inceptionv3", _inc, has_aux=True)


_register_builtins()
