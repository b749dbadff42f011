"""Whisper-style encoder-decoder backbone (audio frontend stubbed).

Per the brief, the conv frontend is a STUB: ``input_layout`` expects
precomputed frame embeddings (B, n_audio_frames, d_model) where the real
model would run its two conv layers over mel spectrograms. Everything
downstream — encoder self-attention stack, decoder with causal
self-attention + cross-attention, tied unembedding — is real.

Decode caches: per-decoder-layer self KV (grows with generated length) and
cross KV (computed once at prefill from the encoder output, then frozen).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.core.telemetry import ATTENTION
from repro.models import attention as A
from repro.models import layers as L
from repro.models.losses import ce_loss
from repro.sharding import constrain



from repro import flags as _flags


def _scan(*args, **kw):
    kw.setdefault("unroll", _flags.scan_unroll_arg())
    return jax.lax.scan(*args, **kw)

def _enc_layer_defs(cfg: ModelConfig) -> L.ParamDefs:
    return {
        "ln1": L.norm_defs(cfg.d_model, cfg.norm_type),
        "attn": A.attn_defs(cfg),
        "ln2": L.norm_defs(cfg.d_model, cfg.norm_type),
        "mlp": L.mlp_defs(cfg.d_model, cfg.d_ff),
    }


def _dec_layer_defs(cfg: ModelConfig) -> L.ParamDefs:
    return {
        "ln1": L.norm_defs(cfg.d_model, cfg.norm_type),
        "self_attn": A.attn_defs(cfg),
        "ln_x": L.norm_defs(cfg.d_model, cfg.norm_type),
        "cross_attn": A.attn_defs(cfg),
        "ln2": L.norm_defs(cfg.d_model, cfg.norm_type),
        "mlp": L.mlp_defs(cfg.d_model, cfg.d_ff),
    }


class EncDecModel:
    def __init__(self, cfg: ModelConfig, *, scan_layers: bool = True,
                 remat: str = "none", attn_impl: str = "jnp"):
        assert cfg.n_encoder_layers > 0 and cfg.n_audio_frames > 0
        self.cfg = cfg
        self.scan_layers = scan_layers
        self.remat = remat
        self.attn_impl = attn_impl

    # ----------------------------------------------------------- parameters
    def param_defs(self) -> L.ParamDefs:
        cfg = self.cfg
        return {
            "embed": L.embed_defs(cfg.vocab_size, cfg.d_model),
            "enc_layers": L.stack_defs(_enc_layer_defs(cfg),
                                       cfg.n_encoder_layers),
            "enc_norm": L.norm_defs(cfg.d_model, cfg.norm_type),
            "dec_layers": L.stack_defs(_dec_layer_defs(cfg), cfg.n_layers),
            "final_norm": L.norm_defs(cfg.d_model, cfg.norm_type),
        }

    def init(self, key: jax.Array):
        return L.init_params(self.param_defs(), key,
                             dtype=jnp.dtype(self.cfg.param_dtype))

    # -------------------------------------------------------------- encoder
    def encode(self, params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.dtype))
        b, t, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))

        def body(carry, lp):
            h = L.apply_norm(lp["ln1"], carry, cfg.norm_type, cfg.norm_eps)
            h = A.full_attention(lp["attn"], h, positions, cfg,
                                 mask_mode="full", impl=self.attn_impl)
            x = carry + h
            h = L.apply_norm(lp["ln2"], x, cfg.norm_type, cfg.norm_eps)
            return x + L.mlp(lp["mlp"], h), None

        if self.remat != "none":
            body = jax.checkpoint(body)
        with ATTENTION.repeated(cfg.n_encoder_layers):
            x, _ = _scan(body, x, params["enc_layers"])
        return L.apply_norm(params["enc_norm"], x, cfg.norm_type, cfg.norm_eps)

    # -------------------------------------------------------------- decoder
    def _dec_layer(self, lp, x, positions, enc_out, return_kv: bool):
        cfg = self.cfg
        h = L.apply_norm(lp["ln1"], x, cfg.norm_type, cfg.norm_eps)
        out = A.full_attention(lp["self_attn"], h, positions, cfg,
                               mask_mode="causal", impl=self.attn_impl,
                               return_kv=return_kv)
        if return_kv:
            out, sk, sv = out
        x = x + out
        h = L.apply_norm(lp["ln_x"], x, cfg.norm_type, cfg.norm_eps)
        out = A.full_attention(lp["cross_attn"], h, positions, cfg,
                               mask_mode="full", kv_x=enc_out,
                               impl=self.attn_impl, return_kv=return_kv)
        if return_kv:
            out, ck, cv = out
        x = x + out
        h = L.apply_norm(lp["ln2"], x, cfg.norm_type, cfg.norm_eps)
        x = x + L.mlp(lp["mlp"], h)
        if return_kv:
            return x, (sk, sv, ck, cv)
        return x

    def decode_fwd(self, params, tokens, enc_out, return_cache: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], tokens, dtype)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        def body(carry, lp):
            out = self._dec_layer(lp, carry, positions, enc_out, return_cache)
            if return_cache:
                x, kv = out
                return x, kv
            return out, None

        if self.remat != "none" and not return_cache:
            body = jax.checkpoint(body)
        with ATTENTION.repeated(cfg.n_layers):
            x, kvs = _scan(body, x, params["dec_layers"])
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        return (x, kvs) if return_cache else x

    # ----------------------------------------------------------- train/serve
    def loss(self, params, batch):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        x = self.decode_fwd(params, batch["tokens"], enc_out)
        loss = ce_loss(x, params["embed"]["embedding"], batch["targets"],
                       chunk=cfg.ce_chunk)
        return loss, {"ce": loss}

    def _logits_last(self, params, x_last):
        logits = jnp.einsum("bd,vd->bv", x_last,
                            params["embed"]["embedding"].astype(x_last.dtype))
        return constrain(logits, "batch", "vocab")

    def prefill(self, params, batch):
        enc_out = self.encode(params, batch["frames"])
        x, (sk, sv, ck, cv) = self.decode_fwd(params, batch["tokens"],
                                              enc_out, return_cache=True)
        cache = {"self_k": sk, "self_v": sv, "cross_k": ck, "cross_v": cv}
        return self._logits_last(params, x[:, -1]), cache

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        self_shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, hd)
        cross_shape = (cfg.n_layers, batch_size, cfg.n_audio_frames,
                       cfg.n_kv_heads, hd)
        return {
            "self_k": jnp.zeros(self_shape, dtype),
            "self_v": jnp.zeros(self_shape, dtype),
            "cross_k": jnp.zeros(cross_shape, dtype),
            "cross_v": jnp.zeros(cross_shape, dtype),
        }

    def decode_step(self, params, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], batch["token"], dtype)
        cache, index = batch["cache"], batch["index"]

        def body(x, layer_in):
            lp, sk, sv, ck, cv = layer_in
            h = L.apply_norm(lp["ln1"], x, cfg.norm_type, cfg.norm_eps)
            out, sk, sv = A.decode_step_attention(lp["self_attn"], h, sk, sv,
                                                  index, cfg)
            x = x + out
            h = L.apply_norm(lp["ln_x"], x, cfg.norm_type, cfg.norm_eps)
            out, _, _ = A.decode_step_attention(lp["cross_attn"], h, ck, cv,
                                                index, cfg, cross=True)
            x = x + out
            h = L.apply_norm(lp["ln2"], x, cfg.norm_type, cfg.norm_eps)
            return x + L.mlp(lp["mlp"], h), (sk, sv)

        x, (nsk, nsv) = _scan(
            body, x, (params["dec_layers"], cache["self_k"], cache["self_v"],
                      cache["cross_k"], cache["cross_v"]))
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = self._logits_last(params, x[:, -1])
        new_cache = dict(cache, self_k=nsk, self_v=nsv)
        return logits, new_cache

    # ------------------------------------------------------------- layouts
    def input_layout(self, kind: str, batch: int, seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        frames = ((batch, cfg.n_audio_frames, d), jnp.dtype(cfg.dtype),
                  ("batch", "seq", "embed"))
        if kind == "train":
            return {
                "frames": frames,
                "tokens": ((batch, seq), jnp.int32, ("batch", "seq")),
                "targets": ((batch, seq), jnp.int32, ("batch", "seq")),
            }
        if kind == "prefill":
            return {
                "frames": frames,
                "tokens": ((batch, seq), jnp.int32, ("batch", "seq")),
            }
        if kind == "decode":
            hd = cfg.resolved_head_dim
            axes = A.cache_logical_axes()
            self_shape = (cfg.n_layers, batch, seq, cfg.n_kv_heads, hd)
            cross_shape = (cfg.n_layers, batch, cfg.n_audio_frames,
                           cfg.n_kv_heads, hd)
            dt = jnp.dtype(cfg.dtype)
            return {
                "token": ((batch, 1), jnp.int32, ("batch", "seq")),
                "cache": {
                    "self_k": (self_shape, dt, axes),
                    "self_v": (self_shape, dt, axes),
                    "cross_k": (cross_shape, dt, axes),
                    "cross_v": (cross_shape, dt, axes),
                },
                "index": ((), jnp.int32, ()),
            }
        raise ValueError(kind)
