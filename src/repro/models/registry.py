"""Model registry: family → class, plus exact analytic parameter counts.

``analytic_param_count`` sums the model's own ``param_defs()`` shape
declarations, so it is exact by construction (no separate bookkeeping to
drift). ``active_only=True`` scales MoE expert tensors by top_k/E — the
MODEL_FLOPS = 6·N_active·D roofline convention.
"""
from __future__ import annotations

import math
from typing import Any

import jax

from repro.config.base import ModelConfig
from repro.models import layers as L


def _families():
    from repro.models.encdec import EncDecModel
    from repro.models.hybrid import HybridModel, InterleavedHybridModel
    from repro.models.ssm import SSMModel
    from repro.models.transformer import DecoderLM, PrefixVLM

    return {
        "dense": DecoderLM,
        "moe": DecoderLM,
        "vlm": PrefixVLM,
        "ssm": SSMModel,
        "hybrid": HybridModel,
        "interleaved": InterleavedHybridModel,
        "audio": EncDecModel,
    }


def build_model(cfg: ModelConfig, *, scan_layers: bool = True,
                remat: str = "none", attn_impl: str = "jnp") -> Any:
    fams = _families()
    if cfg.family not in fams:
        raise KeyError(f"unknown family {cfg.family!r}; known {sorted(fams)}")
    return fams[cfg.family](cfg, scan_layers=scan_layers, remat=remat,
                            attn_impl=attn_impl)


def analytic_param_count(cfg: ModelConfig, active_only: bool = False,
                         include_embeddings: bool = True) -> int:
    model = build_model(cfg)
    defs = model.param_defs()
    total = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, L.Param))
    for path, p in flat:
        keys = [str(getattr(e, "key", "")) for e in path]
        n = math.prod(p.shape)
        if not include_embeddings and any("embed" in k and "layers" not in k
                                          for k in keys[:1]):
            continue
        if active_only and "experts" in p.logical:
            # expert-parallel tensors: only top_k of num_experts are active
            n = int(n * cfg.moe.top_k / max(1, cfg.moe.num_experts))
        total += n
    return int(total)
