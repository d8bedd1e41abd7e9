"""Config registry: ``get_arch(name)`` / ``list_archs()`` / reduced variants.

Reduced variants (``reduced=True``) keep the *family* — block pattern, GQA
ratio shape, MoE routing, norms, activations — but shrink to <=2 layers,
d_model<=512, <=4 experts so a forward/train step runs in seconds on CPU.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

from repro.configs.base import ArchConfig, FLConfig, MLAConfig, MoEConfig, ShapeConfig
from repro.configs.shapes import SHAPES, get_shape

_ARCH_MODULES = {
    "qwen1.5-4b": "repro.configs.qwen1_5_4b",
    "gemma3-4b": "repro.configs.gemma3_4b",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    "phi-3-vision-4.2b": "repro.configs.phi3_vision_4_2b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "whisper-medium": "repro.configs.whisper_medium",
    "minitron-4b": "repro.configs.minitron_4b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}


def list_archs() -> List[str]:
    return sorted(_ARCH_MODULES)


def get_arch(name: str, *, reduced: bool = False) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {list_archs()}")
    cfg = importlib.import_module(_ARCH_MODULES[name]).make_config()
    if reduced:
        cfg = reduce_config(cfg)
    return cfg


def reduce_config(cfg: ArchConfig) -> ArchConfig:
    """Shrink a config to a CPU-smoke-testable variant of the same family."""
    shrink = max(1, cfg.d_model // 256)
    d_model = max(128, cfg.d_model // shrink)
    # keep the head structure's *ratio*: shrink heads to <=4, keep GQA grouping
    ratio = max(1, cfg.num_heads // max(1, cfg.num_kv_heads))
    num_heads = min(4, cfg.num_heads)
    num_kv_heads = max(1, num_heads // min(ratio, num_heads))
    head_dim = d_model // num_heads
    # two layers: take the first two entries of the *cyclic* pattern so both
    # block kinds of hybrid archs are exercised where possible
    num_layers = min(2, cfg.num_layers)
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(
            cfg.moe,
            num_experts=min(4, cfg.moe.num_experts),
            top_k=min(2, cfg.moe.top_k),
            expert_ff=max(64, cfg.moe.expert_ff // shrink) if cfg.moe.expert_ff else 0,
        )
    mla = None
    if cfg.mla is not None:
        mla = MLAConfig(*(max(8, getattr(cfg.mla, f.name) // shrink)
                          for f in dataclasses.fields(MLAConfig)))
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-reduced",
        num_layers=num_layers,
        d_model=d_model,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
        d_ff=0 if cfg.d_ff == 0 else max(256, cfg.d_ff // shrink),
        vocab_size=min(1024, cfg.vocab_size),
        window=min(64, cfg.window) if cfg.window else 0,
        moe=moe,
        mla=mla,
        # keep a leading dense layer and at least one layer after it
        first_dense_layers=min(cfg.first_dense_layers, num_layers - 1),
        encoder_layers=min(2, cfg.encoder_layers),
        encoder_frames=min(16, cfg.encoder_frames),
        image_tokens=min(8, cfg.image_tokens),
        max_position=4096,
    )


def all_configs(*, reduced: bool = False) -> Dict[str, ArchConfig]:
    return {name: get_arch(name, reduced=reduced) for name in list_archs()}


__all__ = [
    "ArchConfig",
    "FLConfig",
    "MLAConfig",
    "MoEConfig",
    "ShapeConfig",
    "SHAPES",
    "get_shape",
    "get_arch",
    "list_archs",
    "reduce_config",
    "all_configs",
]
