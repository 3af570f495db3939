"""Architecture registry: ``--arch <id>`` -> (ModelConfig, ModelApi)."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

from ..configs.base import ModelConfig

__all__ = ["ModelApi", "build_model", "get_config", "list_archs", "ARCHS"]

# arch id -> config module (each exposes CONFIG: ModelConfig)
ARCHS = {
    "granite-8b": "repro.configs.granite_8b",
    "phi4-mini-3.8b": "repro.configs.phi4_mini_3_8b",
    "granite-3-8b": "repro.configs.granite_3_8b",
    "llama3-8b": "repro.configs.llama3_8b",
    "deepseek-v2-lite-16b": "repro.configs.deepseek_v2_lite_16b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "hubert-xlarge": "repro.configs.hubert_xlarge",
    "qwen2-vl-2b": "repro.configs.qwen2_vl_2b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "mamba2-130m": "repro.configs.mamba2_130m",
    "nemotron3-nano-30b-a3b": "repro.configs.nemotron3_nano_30b_a3b",
}


@dataclasses.dataclass
class ModelApi:
    """Uniform functional interface over every architecture family."""

    cfg: ModelConfig
    init: Callable                 # key -> (params, axes)
    abstract_init: Callable        # key -> (ShapeDtypeStruct params, axes)
    forward: Callable              # (params, batch, mesh=None, remat=...) -> logits
    loss_fn: Callable              # (params, batch, mesh=None, remat=...) -> loss
    init_cache: Optional[Callable]  # (batch, max_len) -> (cache, axes)
    decode_step: Optional[Callable]  # (params, cache, tokens, pos, mesh) -> ...
    # (params, batch, mesh=None, remat=...) -> (loss, counts): the loss
    # with the counts the step reports beside it (a family that has
    # some); the train step then differentiates this in place of loss_fn
    loss_and_counts: Optional[Callable] = None


def _lm_api(cfg: ModelConfig) -> ModelApi:
    from . import lm
    return ModelApi(
        cfg=cfg,
        init=lambda key: lm.init(cfg, key),
        abstract_init=lambda key: lm.abstract_init(cfg, key),
        forward=lambda p, b, mesh=None, remat="none", flash=False:
        lm.forward(cfg, p, b, mesh, remat=remat, flash=flash),
        loss_fn=lambda p, b, mesh=None, remat="none": lm.loss_fn(
            cfg, p, b, mesh, remat=remat),
        init_cache=(None if not cfg.is_decoder else
                    (lambda batch, max_len: lm.init_cache(cfg, batch, max_len))),
        decode_step=(None if not cfg.is_decoder else
                     (lambda p, c, t, pos, mesh=None: lm.decode_step(
                         cfg, p, c, t, pos, mesh))),
    )


def _ssm_api(cfg: ModelConfig) -> ModelApi:
    from . import ssm_lm
    return ModelApi(
        cfg=cfg,
        init=lambda key: ssm_lm.init(cfg, key),
        abstract_init=lambda key: ssm_lm.abstract_init(cfg, key),
        forward=lambda p, b, mesh=None, remat="none": ssm_lm.forward(
            cfg, p, b, mesh, remat=remat),
        loss_fn=lambda p, b, mesh=None, remat="none": ssm_lm.loss_fn(
            cfg, p, b, mesh, remat=remat),
        init_cache=lambda batch, max_len: ssm_lm.init_cache(cfg, batch, max_len),
        decode_step=lambda p, c, t, pos, mesh=None: ssm_lm.decode_step(
            cfg, p, c, t, pos, mesh),
    )


def _hybrid_api(cfg: ModelConfig) -> ModelApi:
    from . import hybrid
    return ModelApi(
        cfg=cfg,
        init=lambda key: hybrid.init(cfg, key),
        abstract_init=lambda key: hybrid.abstract_init(cfg, key),
        forward=lambda p, b, mesh=None, remat="none": hybrid.forward(
            cfg, p, b, mesh, remat=remat),
        loss_fn=lambda p, b, mesh=None, remat="none": hybrid.loss_fn(
            cfg, p, b, mesh, remat=remat),
        init_cache=lambda batch, max_len: hybrid.init_cache(cfg, batch, max_len),
        decode_step=lambda p, c, t, pos, mesh=None: hybrid.decode_step(
            cfg, p, c, t, pos, mesh),
    )


def _nemotron_h_api(cfg: ModelConfig) -> ModelApi:
    from . import nemotron_h
    return ModelApi(
        cfg=cfg,
        init=lambda key: nemotron_h.init(cfg, key),
        abstract_init=lambda key: nemotron_h.abstract_init(cfg, key),
        forward=lambda p, b, mesh=None, remat="none": nemotron_h.forward(
            cfg, p, b, mesh, remat=remat)[0],
        loss_fn=lambda p, b, mesh=None, remat="none": nemotron_h.loss_fn(
            cfg, p, b, mesh, remat=remat)[0],
        init_cache=lambda batch, max_len: nemotron_h.init_cache(
            cfg, batch, max_len),
        decode_step=lambda p, c, t, pos, mesh=None: nemotron_h.decode_step(
            cfg, p, c, t, pos, mesh),
        loss_and_counts=lambda p, b, mesh=None, remat="none":
        nemotron_h.loss_fn(cfg, p, b, mesh, remat=remat),
    )


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; choose from {sorted(ARCHS)}")
    return importlib.import_module(ARCHS[arch]).CONFIG


def build_model(cfg_or_arch) -> ModelApi:
    cfg = (get_config(cfg_or_arch) if isinstance(cfg_or_arch, str)
           else cfg_or_arch)
    if cfg.family == "ssm":
        return _ssm_api(cfg)
    if cfg.family == "hybrid":
        return _hybrid_api(cfg)
    if cfg.family == "nemotron_h":
        return _nemotron_h_api(cfg)
    return _lm_api(cfg)


def list_archs():
    return sorted(ARCHS)
