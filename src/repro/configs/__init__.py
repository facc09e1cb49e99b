"""Architecture registry: ``get_config("<arch-id>")`` and the shape table."""

from __future__ import annotations

import importlib

from repro.configs.base import InputShape, ModelConfig  # noqa: F401
from repro.configs.shapes import SHAPES, get_shape  # noqa: F401

_ARCH_MODULES = {
    "qwen2-72b": "repro.configs.qwen2_72b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t",
    "zamba2-2.7b": "repro.configs.zamba2_2p7b",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "minitron-4b": "repro.configs.minitron_4b",
    "h2o-danube-3-4b": "repro.configs.h2o_danube3_4b",
}

ARCHITECTURES = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; have {list(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def get_chip_share(arch: str) -> ModelConfig:
    """The share of ``arch`` one chip holds in a stated deployment (the
    config module's ``CHIP_SHARE``; its docstring states the cut)."""
    get_config(arch)    # validates the name
    share = getattr(importlib.import_module(_ARCH_MODULES[arch]),
                    "CHIP_SHARE", None)
    if share is None:
        raise KeyError(f"{arch!r} has no one-chip share configured")
    return share


def supports_long_context(cfg: ModelConfig) -> bool:
    """Whether the arch runs long_500k *natively* (sub-quadratic without a
    variant toggle).  Others get the explicit SWA variant (DESIGN.md §5)."""
    return cfg.family in ("ssm", "hybrid") or cfg.sliding_window is not None


def long_context_variant(cfg: ModelConfig) -> ModelConfig:
    """The long_500k-ready variant: identity for native sub-quadratic archs,
    sliding-window (4096) toggle for full-attention archs."""
    if supports_long_context(cfg):
        return cfg
    return cfg.with_(name=cfg.name + "+swa4k", sliding_window=4096)
