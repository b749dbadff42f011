"""Decoder-only transformer LM (dense + MoE) and the prefix-LM VLM variant.

Unified model API (duck-typed, shared by every family in the registry):

    param_defs()                      → nested dict of Param declarations
    init(key)                         → param pytree
    loss(params, batch)               → (scalar, metrics dict)   [train_*]
    prefill(params, batch)            → (last_logits, cache)     [prefill_*]
    decode_step(params, batch)        → (logits, new_cache)      [decode_*]
    init_cache(batch, max_len, dtype) → cache pytree
    input_layout(kind, B, S)          → {name: (shape, dtype, logical_axes)}

The layer stack is ``lax.scan`` over stacked layer params (compact HLO —
one layer body regardless of depth, which is what keeps 94-layer dry-run
compiles tractable), with optional per-layer ``jax.checkpoint`` (remat).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.core.telemetry import ATTENTION
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models.losses import ce_loss
from repro.sharding import constrain

REMAT_POLICIES = {
    "none": None,
    "full": "full",
    "dots": "dots",
}



from repro import flags as _flags


def _scan(*args, **kw):
    kw.setdefault("unroll", _flags.scan_unroll_arg())
    return jax.lax.scan(*args, **kw)

def _maybe_remat(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def layer_defs(cfg: ModelConfig) -> L.ParamDefs:
    defs: L.ParamDefs = {
        "ln1": L.norm_defs(cfg.d_model, cfg.norm_type),
        "attn": A.attn_defs(cfg),
        "ln2": L.norm_defs(cfg.d_model, cfg.norm_type),
    }
    if cfg.is_moe:
        defs["moe"] = M.moe_defs(cfg)
    else:
        defs["mlp"] = L.mlp_defs(cfg.d_model, cfg.d_ff)
    return defs


def layer_fwd(lp, x: jax.Array, positions: jax.Array, cfg: ModelConfig,
              mask_mode: str, prefix_len: int, attn_impl: str,
              return_kv: bool = False):
    """One transformer block. Returns (x, aux, (k, v) if return_kv)."""
    h = L.apply_norm(lp["ln1"], x, cfg.norm_type, cfg.norm_eps)
    attn_out = A.full_attention(lp["attn"], h, positions, cfg,
                                mask_mode=mask_mode, prefix_len=prefix_len,
                                impl=attn_impl, return_kv=return_kv)
    if return_kv:
        attn_out, k, v = attn_out
    x = x + attn_out
    h = L.apply_norm(lp["ln2"], x, cfg.norm_type, cfg.norm_eps)
    if cfg.is_moe:
        ffn_out, aux = M.moe_ffn(lp["moe"], h, cfg)
    else:
        ffn_out, aux = L.mlp(lp["mlp"], h), jnp.float32(0.0)
    x = x + ffn_out
    if return_kv:
        return x, aux, k, v
    return x, aux


def layer_decode(lp, x, cache_k, cache_v, index, cfg: ModelConfig):
    """One block, single-token decode. Returns (x, new_k, new_v)."""
    h = L.apply_norm(lp["ln1"], x, cfg.norm_type, cfg.norm_eps)
    attn_out, cache_k, cache_v = A.decode_step_attention(
        lp["attn"], h, cache_k, cache_v, index, cfg)
    x = x + attn_out
    h = L.apply_norm(lp["ln2"], x, cfg.norm_type, cfg.norm_eps)
    if cfg.is_moe:
        ffn_out, _ = M.moe_ffn(lp["moe"], h, cfg)
    else:
        ffn_out = L.mlp(lp["mlp"], h)
    return x + ffn_out, cache_k, cache_v


class DecoderLM:
    """Dense or MoE decoder-only LM."""

    family_mask = "causal"

    def __init__(self, cfg: ModelConfig, *, scan_layers: bool = True,
                 remat: str = "none", attn_impl: str = "jnp"):
        self.cfg = cfg
        self.scan_layers = scan_layers
        self.remat = remat
        self.attn_impl = attn_impl

    # ----------------------------------------------------------- parameters
    def param_defs(self) -> L.ParamDefs:
        cfg = self.cfg
        defs = {
            "embed": L.embed_defs(cfg.vocab_size, cfg.d_model),
            "layers": L.stack_defs(layer_defs(cfg), cfg.n_layers),
            "final_norm": L.norm_defs(cfg.d_model, cfg.norm_type),
        }
        defs.update(L.unembed_defs(cfg.vocab_size, cfg.d_model,
                                   cfg.tie_embeddings))
        return defs

    def init(self, key: jax.Array):
        return L.init_params(self.param_defs(), key,
                             dtype=jnp.dtype(self.cfg.param_dtype))

    # ------------------------------------------------------------- forward
    def _prefix_len(self, batch) -> int:
        return 0

    def _embed_inputs(self, params, batch) -> jax.Array:
        dtype = jnp.dtype(self.cfg.dtype)
        return L.embed(params["embed"], batch["tokens"], dtype)

    def backbone(self, params, x: jax.Array, prefix_len: int = 0,
                 return_cache: bool = False):
        """x: (B, S, D) embedded inputs → final hidden (+ cache)."""
        cfg = self.cfg
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        mask_mode = "prefix" if prefix_len else "causal"

        body = functools.partial(
            layer_fwd, cfg=cfg, positions=positions, mask_mode=mask_mode,
            prefix_len=prefix_len, attn_impl=self.attn_impl,
            return_kv=return_cache)

        def scan_body(carry, lp):
            out = _maybe_remat(lambda c, p: body(p, c), self.remat)(carry, lp)
            if return_cache:
                x, aux, k, v = out
                return x, (aux, k, v)
            x, aux = out
            return x, (aux,)

        if self.scan_layers:
            with ATTENTION.repeated(cfg.n_layers):
                x, ys = _scan(scan_body, x, params["layers"])
        else:
            ys_list = []
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda p: p[i], params["layers"])
                x, y = scan_body(x, lp)
                ys_list.append(y)
            ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys_list)

        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        aux = jnp.mean(ys[0])
        if return_cache:
            cache = {"k": ys[1], "v": ys[2]}  # (L, B, S, KV, hd)
            return x, aux, cache
        return x, aux

    # --------------------------------------------------------------- train
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        prefix = self._prefix_len(batch)
        x, aux = self.backbone(params, x, prefix_len=prefix)
        table = params["embed"]["embedding"] if cfg.tie_embeddings \
            else params["out_embedding"]
        mask = batch.get("loss_mask")
        loss = ce_loss(x, table, batch["targets"], mask=mask,
                       chunk=cfg.ce_chunk)
        total = loss + cfg.moe.load_balance_coef * aux if cfg.is_moe else loss
        metrics = {"ce": loss}
        if cfg.is_moe:
            metrics["aux"] = aux
        return total, metrics

    # ------------------------------------------------------------- serving
    def _logits_last(self, params, x_last: jax.Array) -> jax.Array:
        cfg = self.cfg
        table = params["embed"]["embedding"] if cfg.tie_embeddings \
            else params["out_embedding"]
        logits = jnp.einsum("bd,vd->bv", x_last, table.astype(x_last.dtype))
        return constrain(logits, "batch", "vocab")

    def prefill(self, params, batch):
        x = self._embed_inputs(params, batch)
        x, _, cache = self.backbone(params, x,
                                    prefix_len=self._prefix_len(batch),
                                    return_cache=True)
        return self._logits_last(params, x[:, -1]), cache

    def init_cache(self, batch_size: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
        return A.init_cache(self.cfg, batch_size, max_len, self.cfg.n_layers,
                            dtype)

    def decode_step(self, params, batch):
        """batch: {"token": (B,1) i32, "cache": {...}, "index": i32[]}"""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], batch["token"], dtype)
        cache, index = batch["cache"], batch["index"]

        def scan_body(x, layer_in):
            lp, ck, cv = layer_in
            x, nk, nv = layer_decode(lp, x, ck, cv, index, cfg)
            return x, (nk, nv)

        x, (nk, nv) = _scan(scan_body, x,
                                   (params["layers"], cache["k"], cache["v"]))
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = self._logits_last(params, x[:, -1])
        return logits, {"k": nk, "v": nv}

    # ------------------------------------------------------------- layouts
    def input_layout(self, kind: str, batch: int, seq: int
                     ) -> Dict[str, Any]:
        cfg = self.cfg
        if kind == "train":
            return {
                "tokens": ((batch, seq), jnp.int32, ("batch", "seq")),
                "targets": ((batch, seq), jnp.int32, ("batch", "seq")),
            }
        if kind == "prefill":
            return {
                "tokens": ((batch, seq), jnp.int32, ("batch", "seq")),
            }
        if kind == "decode":
            hd = cfg.resolved_head_dim
            cache_shape = (cfg.n_layers, batch, seq, cfg.n_kv_heads, hd)
            cache_axes = A.cache_logical_axes()
            return {
                "token": ((batch, 1), jnp.int32, ("batch", "seq")),
                "cache": {
                    "k": (cache_shape, jnp.dtype(cfg.dtype), cache_axes),
                    "v": (cache_shape, jnp.dtype(cfg.dtype), cache_axes),
                },
                "index": ((), jnp.int32, ()),
            }
        raise ValueError(kind)


class PrefixVLM(DecoderLM):
    """PaliGemma-style VLM: stubbed SigLIP patch embeddings as a prefix, a
    gemma-style decoder backbone, prefix-LM attention (bidirectional over
    the image prefix), CE on text positions only.

    ``seq`` in every shape cell is the TOTAL length (image prefix + text).
    """

    def _prefix_len(self, batch) -> int:
        return self.cfg.num_image_tokens

    def _embed_inputs(self, params, batch) -> jax.Array:
        dtype = jnp.dtype(self.cfg.dtype)
        text = L.embed(params["embed"], batch["tokens"], dtype)
        patches = batch["patches"].astype(dtype)      # (B, P, D) stub frontend
        x = jnp.concatenate([patches, text], axis=1)
        return constrain(x, "batch", "act_seq", "embed")

    def loss(self, params, batch):
        """targets cover the text positions: (B, S_text)."""
        cfg = self.cfg
        p = cfg.num_image_tokens
        x = self._embed_inputs(params, batch)
        x, aux = self.backbone(params, x, prefix_len=p)
        x_text = x[:, p:]                             # predict text only
        table = params["embed"]["embedding"] if cfg.tie_embeddings \
            else params["out_embedding"]
        loss = ce_loss(x_text, table, batch["targets"], chunk=cfg.ce_chunk)
        return loss, {"ce": loss}

    def prefill(self, params, batch):
        x = self._embed_inputs(params, batch)
        x, _, cache = self.backbone(params, x,
                                    prefix_len=self.cfg.num_image_tokens,
                                    return_cache=True)
        return self._logits_last(params, x[:, -1]), cache

    def input_layout(self, kind: str, batch: int, seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        p = cfg.num_image_tokens
        s_text = max(1, seq - p)
        d = cfg.d_model
        if kind == "train":
            return {
                "tokens": ((batch, s_text), jnp.int32, ("batch", "seq")),
                "targets": ((batch, s_text), jnp.int32, ("batch", "seq")),
                "patches": ((batch, p, d), jnp.dtype(cfg.dtype),
                            ("batch", "seq", "embed")),
            }
        if kind == "prefill":
            return {
                "tokens": ((batch, s_text), jnp.int32, ("batch", "seq")),
                "patches": ((batch, p, d), jnp.dtype(cfg.dtype),
                            ("batch", "seq", "embed")),
            }
        return super().input_layout(kind, batch, seq)
