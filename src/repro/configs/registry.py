"""Architecture registry: ``--arch <id>`` -> ModelConfig (full or smoke).

The assigned architectures, and Mellum2-12B-A2.5B, are addressable by
name; each <arch>.py module also exposes ``smoke()`` with a reduced
same-family config for CPU tests.
"""
from __future__ import annotations

from typing import List

from .base import ModelConfig, ShapeConfig, SHAPES

from . import (rwkv6_3b, olmoe_1b_7b, grok_1_314b, phi_3_vision_4_2b,
               seamless_m4t_medium, minicpm_2b, nemotron_4_15b,
               qwen1_5_110b, granite_34b, hymba_1_5b, mellum2_12b_a2_5b)

_MODULES = {
    "rwkv6-3b": rwkv6_3b,
    "olmoe-1b-7b": olmoe_1b_7b,
    "grok-1-314b": grok_1_314b,
    "phi-3-vision-4.2b": phi_3_vision_4_2b,
    "seamless-m4t-medium": seamless_m4t_medium,
    "minicpm-2b": minicpm_2b,
    "nemotron-4-15b": nemotron_4_15b,
    "qwen1.5-110b": qwen1_5_110b,
    "granite-34b": granite_34b,
    "hymba-1.5b": hymba_1_5b,
    "mellum2-12b-a2.5b": mellum2_12b_a2_5b,
}

ARCHS: List[str] = list(_MODULES)


def get_config(arch: str, smoke: bool = False, **overrides) -> ModelConfig:
    try:
        mod = _MODULES[arch]
    except KeyError:
        raise ValueError(f"unknown arch {arch!r}; have {ARCHS}") from None
    cfg = mod.smoke() if smoke else mod.CONFIG
    return cfg.replace(**overrides) if overrides else cfg


def get_shape(shape: str) -> ShapeConfig:
    try:
        return SHAPES[shape]
    except KeyError:
        raise ValueError(f"unknown shape {shape!r}; have {list(SHAPES)}") \
            from None


def cell_is_runnable(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    """Assignment rules: long_500k only for sub-quadratic archs."""
    if shape.seq_len >= 1 << 19 and not cfg.subquadratic:
        return False
    return True


def all_cells(include_skips: bool = False):
    """Yield (arch, shape_name, runnable) over the 40 assigned cells."""
    for arch in ARCHS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok = cell_is_runnable(cfg, shape)
            if ok or include_skips:
                yield arch, sname, ok
