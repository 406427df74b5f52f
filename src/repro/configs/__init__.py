"""Architecture registry: ``--arch <id>`` resolution.

Module file names use underscores; registry ids keep the assignment's
dashed spelling.
"""
from __future__ import annotations

from repro.configs.base import (
    SHAPES,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    YarnRope,
    ShapeConfig,
    shape_skip_reason,
    smoke_config,
)

from repro.configs.mixtral_8x22b import CONFIG as _mixtral
from repro.configs.qwen2_moe_a2_7b import CONFIG as _qwen2_moe
from repro.configs.chatglm3_6b import CONFIG as _chatglm3
from repro.configs.stablelm_12b import CONFIG as _stablelm
from repro.configs.minicpm_2b import CONFIG as _minicpm
from repro.configs.starcoder2_3b import CONFIG as _starcoder2
from repro.configs.qwen2_vl_7b import CONFIG as _qwen2_vl
from repro.configs.hubert_xlarge import CONFIG as _hubert
from repro.configs.zamba2_2_7b import CONFIG as _zamba2
from repro.configs.mamba2_1_3b import CONFIG as _mamba2
from repro.configs.mellum2_12b_a2_5b import CONFIG as _mellum2

ARCHS = {
    cfg.name: cfg
    for cfg in (
        _mixtral,
        _qwen2_moe,
        _chatglm3,
        _stablelm,
        _minicpm,
        _starcoder2,
        _qwen2_vl,
        _hubert,
        _zamba2,
        _mamba2,
        _mellum2,
    )
}


def get_arch(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES)}")
    return SHAPES[name]


__all__ = [
    "ARCHS",
    "SHAPES",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "YarnRope",
    "ShapeConfig",
    "get_arch",
    "get_shape",
    "shape_skip_reason",
    "smoke_config",
]
