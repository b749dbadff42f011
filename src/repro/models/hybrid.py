"""Hybrids of Mamba2 and attention layers: two layouts.

:class:`InterleavedHybridModel` (granite-4.0-h): one mixer per
``layer_types`` entry, Mamba2 or attention, each layer with its own MLP
and no weights shared; see its docstring.

:class:`HybridModel`, Zamba2-style: Mamba2 backbone + a *shared* attention
block.

One set of attention+MLP parameters is reused at every application point
(every ``shared_block_every`` backbone layers) — Zamba2's parameter-sharing
trick. Layer grouping: the backbone is split into groups of
``shared_block_every`` Mamba2 layers; after each full group the shared
block runs (the trailing partial group, if any, gets no shared block).
Each application point needs its own KV cache at decode (shared *weights*,
distinct *state*).

Deviation from the published Zamba2 noted in DESIGN.md: the real model
concatenates the block input with the original embeddings (2d → d
projection) before the shared block; we feed the current hidden state
directly. LoRA adapters on the shared block are omitted.

``long_500k`` viability: Mamba2 layers carry O(1) state; the shared-block
caches are seq-length but there are only ``n_layers // shared_block_every``
of them (6 for zamba2-1.2b vs 38), and they shard along ``cache_seq`` over
the model axis with the distributed flash-decode merge.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro import flags as _flags
from repro.config.base import ModelConfig
from repro.core.telemetry import ATTENTION, SSD
from repro.models import attention as A
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.losses import ce_loss
from repro.models.transformer import (_maybe_remat, layer_decode, layer_defs,
                                      layer_fwd)
from repro.sharding import constrain


def _scan(*args, **kw):
    kw.setdefault("unroll", _flags.scan_unroll_arg())
    return jax.lax.scan(*args, **kw)


class HybridModel:
    def __init__(self, cfg: ModelConfig, *, scan_layers: bool = True,
                 remat: str = "none", attn_impl: str = "jnp"):
        assert cfg.shared_block_every > 0
        self.cfg = cfg
        self.scan_layers = scan_layers
        self.remat = remat
        self.attn_impl = attn_impl
        self.n_groups = cfg.n_layers // cfg.shared_block_every
        self.tail = cfg.n_layers - self.n_groups * cfg.shared_block_every

    # ----------------------------------------------------------- parameters
    def param_defs(self) -> L.ParamDefs:
        cfg = self.cfg
        block = {
            "ln": L.norm_defs(cfg.d_model, cfg.norm_type),
            "mamba": S.mamba_defs(cfg),
        }
        defs = {
            "embed": L.embed_defs(cfg.vocab_size, cfg.d_model),
            "layers": L.stack_defs(block, cfg.n_layers),
            "shared": layer_defs(cfg),        # ONE attention+MLP block
            "final_norm": L.norm_defs(cfg.d_model, cfg.norm_type),
        }
        defs.update(L.unembed_defs(cfg.vocab_size, cfg.d_model,
                                   cfg.tie_embeddings))
        return defs

    def init(self, key: jax.Array):
        return L.init_params(self.param_defs(), key,
                             dtype=jnp.dtype(self.cfg.param_dtype))

    # ------------------------------------------------------------- forward
    def _mamba_group(self, group_params, x, return_cache: bool):
        def scan_body(carry, lp):
            cfg = self.cfg
            h = L.apply_norm(lp["ln"], carry, cfg.norm_type, cfg.norm_eps)
            out = S.mamba_fwd(lp["mamba"], h, cfg, return_state=return_cache)
            if return_cache:
                out, tails = out
                return carry + out, tails
            fn_out = carry + out
            return fn_out, None

        if self.remat != "none" and not return_cache:
            body = jax.checkpoint(lambda c, p: scan_body(c, p))
        else:
            body = scan_body
        with SSD.repeated(jax.tree.leaves(group_params)[0].shape[0]):
            return _scan(body, x, group_params)

    def _group_slices(self, layers_params):
        """Split stacked layer params into per-group views."""
        k = self.cfg.shared_block_every
        groups = []
        for g in range(self.n_groups):
            groups.append(jax.tree.map(
                lambda p: p[g * k:(g + 1) * k], layers_params))
        if self.tail:
            groups.append(jax.tree.map(
                lambda p: p[self.n_groups * k:], layers_params))
        return groups

    def backbone(self, params, x, return_cache: bool = False):
        cfg = self.cfg
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        groups = self._group_slices(params["layers"])
        mamba_caches, attn_caches = [], []
        aux = jnp.float32(0.0)

        for g, gp in enumerate(groups):
            x, tails = self._mamba_group(gp, x, return_cache)
            if return_cache:
                mamba_caches.append(tails)
            if g < self.n_groups:                      # shared block
                out = layer_fwd(params["shared"], x, positions, cfg,
                                mask_mode="causal", prefix_len=0,
                                attn_impl=self.attn_impl,
                                return_kv=return_cache)
                if return_cache:
                    x, a, k, v = out
                    attn_caches.append((k, v))
                else:
                    x, a = out
                aux = aux + a

        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        if return_cache:
            mamba_cache = jax.tree.map(
                lambda *zs: jnp.concatenate(zs, axis=0), *mamba_caches)
            cache = {
                "mamba": mamba_cache,
                "attn_k": jnp.stack([k for k, _ in attn_caches]),
                "attn_v": jnp.stack([v for _, v in attn_caches]),
            }
            return x, cache
        return x

    # ----------------------------------------------------------- train/serve
    def loss(self, params, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], batch["tokens"], dtype)
        x = self.backbone(params, x)
        table = params["embed"]["embedding"] if cfg.tie_embeddings \
            else params["out_embedding"]
        loss = ce_loss(x, table, batch["targets"], chunk=cfg.ce_chunk)
        return loss, {"ce": loss}

    def _logits_last(self, params, x_last):
        cfg = self.cfg
        table = params["embed"]["embedding"] if cfg.tie_embeddings \
            else params["out_embedding"]
        logits = jnp.einsum("bd,vd->bv", x_last, table.astype(x_last.dtype))
        return constrain(logits, "batch", "vocab")

    def prefill(self, params, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], batch["tokens"], dtype)
        x, cache = self.backbone(params, x, return_cache=True)
        return self._logits_last(params, x[:, -1]), cache

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        mamba = {k: jnp.zeros(shape, dt) for k, (shape, dt, _) in
                 S.mamba_cache_defs(cfg, batch_size, cfg.n_layers,
                                    dtype).items()}
        attn_shape = (self.n_groups, batch_size, max_len, cfg.n_kv_heads, hd)
        return {
            "mamba": mamba,
            "attn_k": jnp.zeros(attn_shape, dtype),
            "attn_v": jnp.zeros(attn_shape, dtype),
        }

    def decode_step(self, params, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], batch["token"], dtype)
        cache, index = batch["cache"], batch["index"]
        k = cfg.shared_block_every

        new_mamba, new_k, new_v = [], [], []
        for g in range(self.n_groups + (1 if self.tail else 0)):
            lo = g * k
            hi = min(lo + k, cfg.n_layers)
            gp = jax.tree.map(lambda p: p[lo:hi], params["layers"])
            gc = jax.tree.map(lambda c: c[lo:hi], cache["mamba"])

            def scan_body(x, layer_in):
                lp, c = layer_in
                h = L.apply_norm(lp["ln"], x, cfg.norm_type, cfg.norm_eps)
                out, nc = S.mamba_decode_step(lp["mamba"], h, c, cfg)
                return x + out, nc

            x, nm = _scan(scan_body, x, (gp, gc))
            new_mamba.append(nm)
            if g < self.n_groups:
                x, nk, nv = layer_decode(params["shared"], x,
                                         cache["attn_k"][g],
                                         cache["attn_v"][g], index, cfg)
                new_k.append(nk)
                new_v.append(nv)

        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = self._logits_last(params, x[:, -1])
        new_cache = {
            "mamba": jax.tree.map(lambda *zs: jnp.concatenate(zs, axis=0),
                                  *new_mamba),
            "attn_k": jnp.stack(new_k),
            "attn_v": jnp.stack(new_v),
        }
        return logits, new_cache

    # ------------------------------------------------------------- layouts
    def input_layout(self, kind: str, batch: int, seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        if kind in ("train", "prefill"):
            out = {"tokens": ((batch, seq), jnp.int32, ("batch", "seq"))}
            if kind == "train":
                out["targets"] = ((batch, seq), jnp.int32, ("batch", "seq"))
            return out
        if kind == "decode":
            hd = cfg.resolved_head_dim
            attn_shape = (self.n_groups, batch, seq, cfg.n_kv_heads, hd)
            attn_axes = A.cache_logical_axes()
            return {
                "token": ((batch, 1), jnp.int32, ("batch", "seq")),
                "cache": {
                    "mamba": S.mamba_cache_defs(cfg, batch, cfg.n_layers,
                                                jnp.dtype(cfg.dtype)),
                    "attn_k": (attn_shape, jnp.dtype(cfg.dtype), attn_axes),
                    "attn_v": (attn_shape, jnp.dtype(cfg.dtype), attn_axes),
                },
                "index": ((), jnp.int32, ()),
            }
        raise ValueError(kind)


# ---------------------------------------------------------------------------
# interleaved hybrid (granite-4.0-h)
# ---------------------------------------------------------------------------

MIXERS = ("mamba", "attention")


def layer_period(kinds: Tuple[str, ...]) -> int:
    """The shortest prefix of ``kinds`` that repeats to the whole."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def _runs(pattern: Tuple[str, ...]) -> List[Tuple[str, int, int]]:
    """(kind, first index among that kind's layers, length) of each run
    of consecutive layers of one kind in ``pattern``."""
    runs, seen = [], dict.fromkeys(MIXERS, 0)
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in runs]


class InterleavedHybridModel:
    """Granite-4.0-H-style hybrid LM (``GraniteMoeHybridForCausalLM``
    without experts).

    Layer ``i`` mixes with a Mamba2 block (:func:`repro.models.ssm
    .mamba_fwd`) or with GQA attention (:func:`repro.models.attention
    .full_attention`, which takes the config's ``position_embedding`` and
    softmax scale) as ``layer_types[i]`` says, and every layer has its
    own SwiGLU MLP (:func:`repro.models.layers.mlp`)::

        x = x + rm · mixer(RMSNorm(x))
        x = x + rm · mlp(RMSNorm(x))

    with ``rm`` the ``residual_multiplier``. The token embeddings are
    multiplied by ``embedding_multiplier`` and the logits of the tied or
    untied head divided by ``logits_scaling``; at 1 each is left out.

    Weights: each kind's layers are stacked on one leading axis, in order
    (``layers/mamba/...``, ``layers/attention/...``), each layer
    ``{"ln1", "mixer", "ln2", "mlp"}``. The forward pass scans over whole
    periods of the layer pattern (:func:`layer_period`) and, inside one,
    over each run of consecutive layers of one kind, so the compiled
    program holds one body per run whatever the depth; with remat each
    layer is a ``jax.checkpoint``, as in the decoder.
    """

    def __init__(self, cfg: ModelConfig, *, scan_layers: bool = True,
                 remat: str = "none", attn_impl: str = "jnp"):
        kinds = tuple(cfg.layer_types)
        if len(kinds) != cfg.n_layers or not set(kinds) <= set(MIXERS):
            raise ValueError(f"layer_types {kinds} must give one of "
                             f"{MIXERS} for each of {cfg.n_layers} layers")
        self.cfg = cfg
        self.scan_layers = scan_layers
        self.remat = remat
        self.attn_impl = attn_impl
        period = layer_period(kinds)
        self.n_periods = cfg.n_layers // period
        self.runs = _runs(kinds[:period])
        self.counts = {k: kinds.count(k) for k in MIXERS if k in kinds}

    # ----------------------------------------------------------- parameters
    def _block_defs(self, kind: str) -> L.ParamDefs:
        cfg = self.cfg
        return {
            "ln1": L.norm_defs(cfg.d_model, cfg.norm_type),
            "mixer": (S.mamba_defs(cfg) if kind == "mamba"
                      else A.attn_defs(cfg)),
            "ln2": L.norm_defs(cfg.d_model, cfg.norm_type),
            "mlp": L.mlp_defs(cfg.d_model, cfg.d_ff),
        }

    def param_defs(self) -> L.ParamDefs:
        cfg = self.cfg
        defs = {
            "embed": L.embed_defs(cfg.vocab_size, cfg.d_model),
            "layers": {k: L.stack_defs(self._block_defs(k), n)
                       for k, n in self.counts.items()},
            "final_norm": L.norm_defs(cfg.d_model, cfg.norm_type),
        }
        defs.update(L.unembed_defs(cfg.vocab_size, cfg.d_model,
                                   cfg.tie_embeddings))
        return defs

    def init(self, key: jax.Array):
        return L.init_params(self.param_defs(), key,
                             dtype=jnp.dtype(self.cfg.param_dtype))

    # ------------------------------------------------------------- forward
    def _residual(self, y: jax.Array) -> jax.Array:
        m = self.cfg.residual_multiplier
        return y if m == 1.0 else y * jnp.asarray(m, y.dtype)

    def _embed(self, params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        m = cfg.embedding_multiplier
        return x if m == 1.0 else x * jnp.asarray(m, x.dtype)

    def _layer(self, kind: str, lp, x, positions, return_cache: bool):
        """One layer; returns (x, its cache or None)."""
        cfg = self.cfg
        h = L.apply_norm(lp["ln1"], x, cfg.norm_type, cfg.norm_eps)
        cache = None
        if kind == "mamba":
            out = S.mamba_fwd(lp["mixer"], h, cfg, return_state=return_cache)
            if return_cache:
                out, cache = out
        else:
            out = A.full_attention(lp["mixer"], h, positions, cfg,
                                   mask_mode="causal", impl=self.attn_impl,
                                   return_kv=return_cache)
            if return_cache:
                out, k, v = out
                cache = {"k": k, "v": v}
        return self._mlp_after(lp, x, out), cache

    def _mlp_after(self, lp, x, mixed):
        """The layer's rest once its mixer gave ``mixed``: the residual
        add, then norm, MLP and residual add."""
        cfg = self.cfg
        x = x + self._residual(mixed)
        h = L.apply_norm(lp["ln2"], x, cfg.norm_type, cfg.norm_eps)
        return x + self._residual(L.mlp(lp["mlp"], h))

    def _period(self, x, pp, positions, return_cache: bool):
        """One period: each run of one kind scanned over its layers.
        Returns (x, {kind: its layers' caches stacked})."""
        caches: Dict[str, list] = {k: [] for k in self.counts}
        for kind, lo, n in self.runs:
            fn = lambda c, lp, kind=kind: self._layer(kind, lp, c, positions,
                                                       return_cache)
            if not return_cache:
                fn = _maybe_remat(fn, self.remat)
            run = jax.tree.map(lambda p: p[lo:lo + n], pp[kind])
            with ATTENTION.repeated(n), SSD.repeated(n):
                x, ys = _scan(fn, x, run)
            caches[kind].append(ys)
        if not return_cache:
            return x, None
        return x, {k: jax.tree.map(lambda *zs: jnp.concatenate(zs), *ys)
                   for k, ys in caches.items()}

    def backbone(self, params, x, return_cache: bool = False):
        cfg = self.cfg
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        periods = jax.tree.map(
            lambda p: p.reshape((self.n_periods, -1) + p.shape[1:]),
            params["layers"])
        body = lambda c, pp: self._period(c, pp, positions, return_cache)
        if self.scan_layers:
            with ATTENTION.repeated(self.n_periods), \
                    SSD.repeated(self.n_periods):
                x, caches = _scan(body, x, periods)
        else:
            outs = []
            for i in range(self.n_periods):
                x, c = body(x, jax.tree.map(lambda p: p[i], periods))
                outs.append(c)
            caches = (jax.tree.map(lambda *zs: jnp.stack(zs), *outs)
                      if return_cache else None)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        if not return_cache:
            return x
        # (periods, per period, ...) → one stack of each kind's layers
        return x, jax.tree.map(lambda c: c.reshape((-1,) + c.shape[2:]),
                               caches)

    def _table(self, params):
        return params["embed"]["embedding"] if self.cfg.tie_embeddings \
            else params["out_embedding"]

    # ----------------------------------------------------------- train/serve
    def loss(self, params, batch):
        cfg = self.cfg
        x = self.backbone(params, self._embed(params, batch["tokens"]))
        loss = ce_loss(x, self._table(params), batch["targets"],
                       chunk=cfg.ce_chunk, logits_scaling=cfg.logits_scaling)
        return loss, {"ce": loss}

    def _logits_last(self, params, x_last):
        table = self._table(params)
        logits = jnp.einsum("bd,vd->bv", x_last, table.astype(x_last.dtype))
        if self.cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(self.cfg.logits_scaling,
                                          logits.dtype)
        return constrain(logits, "batch", "vocab")

    def prefill(self, params, batch):
        x = self._embed(params, batch["tokens"])
        x, caches = self.backbone(params, x, return_cache=True)
        cache = {}
        if "mamba" in caches:
            cache["mamba"] = caches["mamba"]
        if "attention" in caches:
            cache["attn_k"] = caches["attention"]["k"]
            cache["attn_v"] = caches["attention"]["v"]
        return self._logits_last(params, x[:, -1]), cache

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        return jax.tree.map(
            lambda leaf: jnp.zeros(leaf[0], leaf[1]),
            self.input_layout("decode", batch_size, max_len)["cache"],
            is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3)

    def decode_step(self, params, batch):
        """One token through every layer in order (no scan: decode is
        not a path this model is measured on)."""
        cfg = self.cfg
        x = self._embed(params, batch["token"])
        cache, index = batch["cache"], batch["index"]
        layers = params["layers"]
        seen = dict.fromkeys(self.counts, 0)
        new = {"mamba": [], "attn_k": [], "attn_v": []}
        for kind in cfg.layer_types:
            i = seen[kind]
            seen[kind] += 1
            lp = jax.tree.map(lambda p: p[i], layers[kind])
            h = L.apply_norm(lp["ln1"], x, cfg.norm_type, cfg.norm_eps)
            if kind == "mamba":
                out, c = S.mamba_decode_step(
                    lp["mixer"], h,
                    jax.tree.map(lambda c: c[i], cache["mamba"]), cfg)
                new["mamba"].append(c)
            else:
                out, k, v = A.decode_step_attention(
                    lp["mixer"], h, cache["attn_k"][i], cache["attn_v"][i],
                    index, cfg)
                new["attn_k"].append(k)
                new["attn_v"].append(v)
            x = self._mlp_after(lp, x, out)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        new_cache = {k: (jax.tree.map(lambda *zs: jnp.stack(zs), *v)
                         if k == "mamba" else jnp.stack(v))
                     for k, v in new.items() if v}
        return self._logits_last(params, x[:, -1]), new_cache

    # ------------------------------------------------------------- layouts
    def input_layout(self, kind: str, batch: int, seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        if kind in ("train", "prefill"):
            out = {"tokens": ((batch, seq), jnp.int32, ("batch", "seq"))}
            if kind == "train":
                out["targets"] = ((batch, seq), jnp.int32, ("batch", "seq"))
            return out
        if kind == "decode":
            dtype = jnp.dtype(cfg.dtype)
            cache = {}
            if "mamba" in self.counts:
                cache["mamba"] = S.mamba_cache_defs(
                    cfg, batch, self.counts["mamba"], dtype)
            if "attention" in self.counts:
                shape = (self.counts["attention"], batch, seq, cfg.n_kv_heads,
                         cfg.resolved_head_dim)
                for k in ("attn_k", "attn_v"):
                    cache[k] = (shape, dtype, A.cache_logical_axes())
            return {"token": ((batch, 1), jnp.int32, ("batch", "seq")),
                    "cache": cache, "index": ((), jnp.int32, ())}
        raise ValueError(kind)
