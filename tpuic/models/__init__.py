"""Model registry.

``create_model(name, num_classes)`` is the framework equivalent of
``Classifier(name, num_classes)`` in reference nn/classifier.py:8-34. Accepted
names cover the reference's selector strings ('resnet50', 'resnet101',
'inceptionv3', 'efficientnet-b3' — nn/classifier.py:11-23) plus the
BASELINE.md parity-config additions ('resnet18', 'efficientnet-b0',
'vit-b16').
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax.numpy as jnp

from tpuic.config import ModelConfig
from tpuic.models.classifier import Classifier
from tpuic.models import resnet as _resnet
from tpuic.models import efficientnet as _effnet
from tpuic.models import inception as _inception
from tpuic.models import vit as _vit
from tpuic.models import ouro as _ouro

# name -> (factory(num_classes, dtype, param_dtype, bn_momentum, bn_eps),
#          has_aux)
_REGISTRY: Dict[str, Tuple[Callable[..., Any], bool]] = {}


def register(name: str, factory: Callable[..., Any], has_aux: bool = False):
    _REGISTRY[name] = (factory, has_aux)


def available_models():
    return sorted(_REGISTRY)


# Single source of truth: the module whose attention dispatch consumes it.
from tpuic.models.vit import ATTENTION_IMPLS  # noqa: E402,F401


def create_backbone(name: str, num_classes: int = 0, *, dtype=jnp.float32,
                    param_dtype=jnp.float32, bn_momentum: float = 0.9,
                    bn_eps: float = 1e-5, attention: str = "dense",
                    mesh=None, bn_f32_stats: bool = True,
                    drop_path: float = 0.0, remat_core: bool = False,
                    remat_blocks: bool = False, remat_mlp: bool = False,
                    fused_conv_bn: bool = False):
    if name not in _REGISTRY:
        raise ValueError(f"unknown model '{name}'; available: {available_models()}")
    if attention not in ATTENTION_IMPLS:
        raise ValueError(f"unknown attention impl '{attention}'; "
                         f"available: {ATTENTION_IMPLS}")
    factory, has_aux = _REGISTRY[name]
    return factory(num_classes=num_classes, dtype=dtype,
                   param_dtype=param_dtype, bn_momentum=bn_momentum,
                   bn_eps=bn_eps, attention=attention, mesh=mesh,
                   bn_f32_stats=bn_f32_stats, drop_path=drop_path,
                   remat_core=remat_core, remat_blocks=remat_blocks,
                   remat_mlp=remat_mlp,
                   fused_conv_bn=fused_conv_bn), has_aux


def create_model(name: str, num_classes: int, *, head_widths=(128, 64, 32),
                 dtype="bfloat16", param_dtype="float32",
                 bn_momentum: float = 0.9, bn_eps: float = 1e-5,
                 attention: str = "dense", mesh=None,
                 bn_f32_stats: bool = True,
                 drop_path: float = 0.0,
                 remat_core: bool = False,
                 remat_blocks: bool = False,
                 remat_mlp: bool = False,
                 fused_conv_bn: bool = False) -> Classifier:
    dt, pdt = jnp.dtype(dtype), jnp.dtype(param_dtype)
    backbone, has_aux = create_backbone(name, num_classes, dtype=dt,
                                        param_dtype=pdt,
                                        bn_momentum=bn_momentum, bn_eps=bn_eps,
                                        attention=attention, mesh=mesh,
                                        bn_f32_stats=bn_f32_stats,
                                        drop_path=drop_path,
                                        remat_core=remat_core,
                                        remat_blocks=remat_blocks,
                                        remat_mlp=remat_mlp,
                                        fused_conv_bn=fused_conv_bn)
    return Classifier(backbone=backbone, num_classes=num_classes,
                      head_widths=tuple(head_widths), has_aux=has_aux,
                      dtype=dt, param_dtype=pdt)


def create_model_from_config(cfg: ModelConfig, mesh=None) -> Classifier:
    return create_model(cfg.name, cfg.num_classes, head_widths=cfg.head_widths,
                        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        bn_momentum=cfg.bn_momentum, bn_eps=cfg.bn_eps,
                        attention=cfg.attention, mesh=mesh,
                        bn_f32_stats=cfg.bn_f32_stats,
                        drop_path=cfg.drop_path,
                        # 'attention' selective remat lives in the model
                        # (ViT remat_core), not a step-level jax.checkpoint
                        # (train/step.py resolve_remat_policy).
                        remat_core=(cfg.remat
                                    and cfg.remat_policy == "attention"),
                        # 'blocks' per-block remat likewise lives in the
                        # model (ViT remat_blocks, nn.remat per encoder
                        # block) — the long-context memory mode.
                        remat_blocks=(cfg.remat
                                      and cfg.remat_policy == "blocks"),
                        # 'gelu' likewise: MlpUpGelu under nn.remat (ViT
                        # remat_mlp) — the mlp_up pre-activation is never
                        # a residual; see models/vit.py MlpUpGelu.
                        remat_mlp=(cfg.remat
                                   and cfg.remat_policy == "gelu"),
                        # Inference-only Pallas fused conv+BN+ReLU for
                        # the ResNet family (kernels/conv_bn_relu.py);
                        # training and non-ResNet backbones ignore it.
                        fused_conv_bn=cfg.fused_conv_bn)


def _register_builtins():
    def _rn(factory, **extra):
        def make(*, num_classes, dtype, param_dtype, bn_momentum, bn_eps,
                 attention, mesh, bn_f32_stats, drop_path, remat_core,
                 remat_blocks, remat_mlp, fused_conv_bn):
            del (num_classes, attention, mesh, drop_path, remat_core,
                 remat_blocks, remat_mlp)
            return factory(dtype=dtype, param_dtype=param_dtype,
                           bn_momentum=bn_momentum, bn_eps=bn_eps,
                           bn_f32_stats=bn_f32_stats,
                           fused_inference=fused_conv_bn, **extra)
        return make

    register("resnet18", _rn(_resnet.resnet18))
    register("resnet34", _rn(_resnet.resnet34))
    register("resnet50", _rn(_resnet.resnet50))
    register("resnet101", _rn(_resnet.resnet101))
    register("resnet152", _rn(_resnet.resnet152))
    register("resnet18-cifar", _rn(_resnet.resnet18, small_stem=True))
    # MLPerf-style space-to-depth stem: identical math to resnet50 (the
    # 7x7/s2 stem re-indexed as 4x4/s1 on [H/2,W/2,12]), better MXU layout;
    # convert standard stem weights with models.resnet.s2d_stem_kernel.
    register("resnet50-s2d", _rn(_resnet.resnet50, space_to_depth=True))

    def _eff(variant):
        def make(*, num_classes, dtype, param_dtype, bn_momentum, bn_eps,
                 attention, mesh, bn_f32_stats, drop_path, remat_core,
                 remat_blocks, remat_mlp, fused_conv_bn):
            # torch effnet: eps 1e-3; f32 stats kept (experiment is
            # ResNet-scoped, ModelConfig.bn_f32_stats); fused conv+BN is
            # ResNet-only too.
            del (num_classes, bn_eps, attention, mesh, bn_f32_stats,
                 drop_path, remat_core, remat_blocks, remat_mlp,
                 fused_conv_bn)
            return _effnet.efficientnet(variant, dtype=dtype,
                                        param_dtype=param_dtype,
                                        bn_momentum=bn_momentum)
        return make

    for v in ("b0", "b1", "b2", "b3", "b4", "b5", "b6", "b7"):
        register(f"efficientnet-{v}", _eff(v))

    def _vit_factory(ctor):
        def make(*, num_classes, dtype, param_dtype, bn_momentum, bn_eps,
                 attention, mesh, bn_f32_stats, drop_path, remat_core,
                 remat_blocks, remat_mlp, fused_conv_bn):
            del num_classes, bn_momentum, bn_eps, bn_f32_stats  # no BN in ViT
            del fused_conv_bn  # ResNet-only
            return ctor(dtype=dtype, param_dtype=param_dtype,
                        attention=attention, mesh=mesh, drop_path=drop_path,
                        remat_core=remat_core, remat_blocks=remat_blocks,
                        remat_mlp=remat_mlp)
        return make

    register("vit-b16", _vit_factory(_vit.vit_b16))
    register("vit-l16", _vit_factory(_vit.vit_l16))
    register("vit-b32", _vit_factory(_vit.vit_b32))
    register("vit-l32", _vit_factory(_vit.vit_l32))
    register("vit-s16", _vit_factory(_vit.vit_s16))
    register("vit-tiny", _vit_factory(_vit.vit_tiny))
    # Switch-MoE variants (models/moe.py): expert-parallel over the mesh
    # 'model' axis; beyond-parity (reference is dense-only, SURVEY.md §2c).
    register("vit-s16-moe", _vit_factory(_vit.vit_s16_moe))
    register("vit-tiny-moe", _vit_factory(_vit.vit_tiny_moe))

    def _looped(ctor, **extra):
        def make(*, num_classes, dtype, param_dtype, bn_momentum, bn_eps,
                 attention, mesh, bn_f32_stats, drop_path, remat_core,
                 remat_blocks, remat_mlp, fused_conv_bn):
            # RMSNorm only; the causal rotary core is dense (196 tokens)
            del (num_classes, bn_momentum, bn_eps, bn_f32_stats, attention,
                 mesh, drop_path, remat_core, remat_mlp, fused_conv_bn)
            return ctor(dtype=dtype, param_dtype=param_dtype,
                        remat_blocks=remat_blocks, **extra)
        return make

    # Looped decoder stack as a backbone (models/ouro.py): Ouro-2.6B at its
    # published depth, and the first pipeline stage of eight (6 of the 48
    # layers; every width and the four passes as published).
    register("ouro-2.6b", _looped(_ouro.ouro_2_6b))
    register("ouro-2.6b-l6", _looped(_ouro.ouro_2_6b, depth=6))
    register("ouro-tiny", _looped(_ouro.ouro_tiny))

    def _inc(*, num_classes, dtype, param_dtype, bn_momentum, bn_eps,
             attention, mesh, bn_f32_stats, drop_path, remat_core,
             remat_blocks, remat_mlp, fused_conv_bn):
        # torch inception: eps 1e-3 (module default); f32 stats kept;
        # fused conv+BN is ResNet-only.
        del (bn_eps, attention, mesh, bn_f32_stats, drop_path,
             remat_core, remat_blocks, remat_mlp, fused_conv_bn)
        return _inception.InceptionV3(aux_classes=num_classes, dtype=dtype,
                                      param_dtype=param_dtype,
                                      bn_momentum=bn_momentum)

    register("inceptionv3", _inc, has_aux=True)


_register_builtins()
