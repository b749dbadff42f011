"""Mamba2 (state-space duality) blocks and the attention-free SSM LM.

Block structure (Mamba2, arXiv:2405.21060):

    x, z, B, C, Δ = projections of the input
    x, B, C       = causal depthwise conv (width 4) + SiLU
    y             = SSD(x·heads, Δ, A, B, C) + D∘x          (chunked scan)
    out           = out_proj( RMSNorm(y ⊙ SiLU(z)) )

The train/prefill path uses the *chunked* SSD algorithm (same math as the
Pallas kernel in :mod:`repro.kernels.ssd`, vectorized jnp here so it lowers
on any backend); decode is the exact O(1)-per-step recurrence on a
(B, H, N, P) state — this is what makes the ``long_500k`` cell linear.

Sharding: SSD heads ride the model axis (``ssm_heads``), d_inner
projections ride ``mlp``; the (N,) state dim and B/C projections are
replicated (N = 64..128, negligible).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.core.telemetry import SSD, scope
from repro.models import layers as L
from repro.models.losses import ce_loss
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# chunked SSD (jnp twin of kernels/ssd)
# ---------------------------------------------------------------------------


from repro import flags as _flags


def _scan(*args, **kw):
    kw.setdefault("unroll", _flags.scan_unroll_arg())
    return jax.lax.scan(*args, **kw)

def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                cm: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,L,H,P) · dt: (B,L,H) · a: (H,) · bm/cm: (B,L,N) → (y, state)."""
    b, l, h, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // q

    x32 = x.astype(jnp.float32).reshape(b, nc, q, h, p)
    dt32 = dt.astype(jnp.float32).reshape(b, nc, q, h)
    bm32 = bm.astype(jnp.float32).reshape(b, nc, q, n)
    cm32 = cm.astype(jnp.float32).reshape(b, nc, q, n)
    a32 = a.astype(jnp.float32)

    s0 = (jnp.zeros((b, h, n, p), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    rows = jnp.arange(q)[:, None]
    cols = jnp.arange(q)[None, :]
    tri = cols <= rows                                  # (Q, Q)

    # checkpointed: keeps only the (B,H,N,P) carry per chunk in the scan
    # backward; the (b,Q,Q,h) decay tensors are recomputed chunk-by-chunk
    @jax.checkpoint
    def body(state, inp):
        xq, dtq, bq, cq = inp                           # (b,Q,h,p) (b,Q,h) (b,Q,n)
        da = dtq * a32                                  # (b,Q,h)
        cum = jnp.cumsum(da, axis=1)                    # (b,Q,h) inclusive
        total = cum[:, -1]                              # (b,h)

        # mask BEFORE exp: for s > t the raw exponent is large-positive
        # (cum decreases), and exp→inf followed by where(…, 0) still NaNs
        # the backward (inf · 0 cotangent)
        darg = cum[:, :, None, :] - cum[:, None, :, :]            # (b,t,s,h)
        ldec = jnp.exp(jnp.where(tri[None, :, :, None], darg, -60.0))
        ldec = jnp.where(tri[None, :, :, None], ldec, 0.0)
        scores = jnp.einsum("btn,bsn->bts", cq, bq)               # (b,t,s)
        sc = scores[..., None] * ldec * dtq[:, None, :, :]        # (b,t,s,h)
        y = jnp.einsum("btsh,bshp->bthp", sc, xq)

        c_scaled = cq[:, :, None, :] * jnp.exp(cum)[..., None]    # (b,t,h,n)
        y = y + jnp.einsum("bthn,bhnp->bthp", c_scaled, state)

        b_scaled = bq[:, :, None, :] * (dtq * jnp.exp(
            total[:, None, :] - cum))[..., None]                  # (b,s,h,n)
        state = jnp.exp(total)[:, :, None, None] * state + \
            jnp.einsum("bshn,bshp->bhnp", b_scaled, xq)
        return state, y

    xs = (jnp.moveaxis(x32, 1, 0), jnp.moveaxis(dt32, 1, 0),
          jnp.moveaxis(bm32, 1, 0), jnp.moveaxis(cm32, 1, 0))
    state, ys = _scan(body, s0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, lp, h, p)[:, :l]
    return y.astype(x.dtype), state


def ssd_decode_step(state: jax.Array, xt: jax.Array, dtt: jax.Array,
                    a: jax.Array, bt: jax.Array, ct: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Exact recurrence, one step. state: (B,H,N,P) · xt: (B,H,P) ·
    dtt: (B,H) · bt/ct: (B,N)."""
    decay = jnp.exp(dtt * a[None])                       # (B,H)
    state = state * decay[:, :, None, None] + (
        dtt[:, :, None, None] * bt[:, None, :, None] * xt[:, :, None, :])
    y = jnp.einsum("bn,bhnp->bhp", ct, state)
    return y, state


# ---------------------------------------------------------------------------
# depthwise causal conv
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, L, C) · w: (W, C) · b: (C,) → (B, L, C), left-padded causal."""
    width = w.shape[0]
    out = jax.lax.conv_general_dilated(
        x, w[:, None, :],
        window_strides=(1,), padding=[(width - 1, 0)],
        dimension_numbers=("NLC", "LIO", "NLC"),
        feature_group_count=x.shape[-1])
    return out + b


def conv_decode_step(cache: jax.Array, xt: jax.Array, w: jax.Array,
                     b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """cache: (B, W−1, C) past inputs · xt: (B, C) → (yt (B, C), new cache)."""
    window = jnp.concatenate([cache, xt[:, None]], axis=1)   # (B, W, C)
    yt = jnp.einsum("bwc,wc->bc", window, w) + b
    return yt, window[:, 1:]


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def mamba_defs(cfg: ModelConfig, d_model: Optional[int] = None) -> L.ParamDefs:
    d = d_model or cfg.d_model
    s = cfg.ssm
    d_inner = s.expand * d
    h = d_inner // s.head_dim
    n, w = s.state_dim, s.conv_width
    return {
        "in_x": L.Param((d, d_inner), ("embed", "mlp"), init="fan_in"),
        "in_z": L.Param((d, d_inner), ("embed", "mlp"), init="fan_in"),
        "in_b": L.Param((d, n), ("embed", "ssm_state"), init="fan_in"),
        "in_c": L.Param((d, n), ("embed", "ssm_state"), init="fan_in"),
        "in_dt": L.Param((d, h), ("embed", "ssm_heads"), init="fan_in"),
        "dt_bias": L.Param((h,), ("ssm_heads",), init="zeros"),
        "a_log": L.Param((h,), ("ssm_heads",), init="ssm_a"),
        "d_skip": L.Param((h,), ("ssm_heads",), init="ones"),
        "conv_x_w": L.Param((w, d_inner), ("conv", "mlp"), init="fan_in"),
        "conv_x_b": L.Param((d_inner,), ("mlp",), init="zeros"),
        "conv_b_w": L.Param((w, n), ("conv", "ssm_state"), init="fan_in"),
        "conv_b_b": L.Param((n,), ("ssm_state",), init="zeros"),
        "conv_c_w": L.Param((w, n), ("conv", "ssm_state"), init="fan_in"),
        "conv_c_b": L.Param((n,), ("ssm_state",), init="zeros"),
        "gate_norm": L.Param((d_inner,), ("mlp",), init="ones"),
        "out": L.Param((d_inner, d), ("mlp", "embed"), init="fan_in"),
    }


def _project(params, x):
    dtype = x.dtype
    xi = jnp.einsum("bsd,de->bse", x, params["in_x"].astype(dtype))
    z = jnp.einsum("bsd,de->bse", x, params["in_z"].astype(dtype))
    bm = jnp.einsum("bsd,dn->bsn", x, params["in_b"].astype(dtype))
    cm = jnp.einsum("bsd,dn->bsn", x, params["in_c"].astype(dtype))
    dt = jnp.einsum("bsd,dh->bsh", x, params["in_dt"].astype(dtype))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    return xi, z, bm, cm, dt


def mamba_fwd(params, x: jax.Array, cfg: ModelConfig,
              return_state: bool = False):
    """x: (B, S, D) → (out, (ssm_state, conv tails) if return_state).

    Traced under the ``lm.ssm`` scope, the SSD scan under ``lm.ssd``
    inside it; each call counts once in :data:`SSD` by its path."""
    with scope("lm.ssm"):
        return _mamba_fwd(params, x, cfg, return_state)


def _mamba_fwd(params, x, cfg: ModelConfig, return_state: bool):
    s = cfg.ssm
    b, l, d = x.shape
    d_inner = params["in_x"].shape[1]
    h = d_inner // s.head_dim

    xi, z, bm, cm, dt = _project(params, x)
    xi = constrain(xi, "batch", "seq", "mlp")

    xi_conv = jax.nn.silu(causal_conv(xi, params["conv_x_w"].astype(xi.dtype),
                                      params["conv_x_b"].astype(xi.dtype)))
    bm_conv = jax.nn.silu(causal_conv(bm, params["conv_b_w"].astype(bm.dtype),
                                      params["conv_b_b"].astype(bm.dtype)))
    cm_conv = jax.nn.silu(causal_conv(cm, params["conv_c_w"].astype(cm.dtype),
                                      params["conv_c_b"].astype(cm.dtype)))

    xh = xi_conv.reshape(b, l, h, s.head_dim)
    xh = constrain(xh, "batch", "seq", "ssm_heads", "head_dim")
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    SSD.add("chunked")
    with scope("lm.ssd"):
        y, state = ssd_chunked(xh, dt, a, bm_conv, cm_conv, s.chunk_size)
    y = y + params["d_skip"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(b, l, d_inner)

    y = L.rms_norm(y * jax.nn.silu(z), params["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out"].astype(y.dtype))
    out = constrain(out, "batch", "act_seq", "embed")
    if return_state:
        tails = {
            "ssm": state,                                # (B, H, N, P) f32
            "conv_x": xi[:, -(s.conv_width - 1):],        # pre-conv tails
            "conv_b": bm[:, -(s.conv_width - 1):],
            "conv_c": cm[:, -(s.conv_width - 1):],
        }
        return out, tails
    return out


def mamba_decode_step(params, x: jax.Array, cache: Dict[str, jax.Array],
                      cfg: ModelConfig):
    """x: (B, 1, D) one token. cache: {"ssm","conv_x","conv_b","conv_c"}."""
    s = cfg.ssm
    b = x.shape[0]
    d_inner = params["in_x"].shape[1]
    h = d_inner // s.head_dim

    xi, z, bm, cm, dt = _project(params, x)
    xi, z = xi[:, 0], z[:, 0]
    bm, cm, dt = bm[:, 0], cm[:, 0], dt[:, 0]

    xc, conv_x = conv_decode_step(cache["conv_x"], xi,
                                  params["conv_x_w"].astype(xi.dtype),
                                  params["conv_x_b"].astype(xi.dtype))
    bc, conv_b = conv_decode_step(cache["conv_b"], bm,
                                  params["conv_b_w"].astype(bm.dtype),
                                  params["conv_b_b"].astype(bm.dtype))
    cc, conv_c = conv_decode_step(cache["conv_c"], cm,
                                  params["conv_c_w"].astype(cm.dtype),
                                  params["conv_c_b"].astype(cm.dtype))
    xc, bc, cc = jax.nn.silu(xc), jax.nn.silu(bc), jax.nn.silu(cc)

    xh = xc.reshape(b, h, s.head_dim)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    y, ssm = ssd_decode_step(cache["ssm"], xh.astype(jnp.float32),
                             dt, a, bc.astype(jnp.float32),
                             cc.astype(jnp.float32))
    y = y.astype(x.dtype) + params["d_skip"].astype(x.dtype)[None, :, None] * xh
    y = y.reshape(b, 1, d_inner)

    y = L.rms_norm(y * jax.nn.silu(z)[:, None], params["gate_norm"],
                   cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out"].astype(y.dtype))
    new_cache = {"ssm": ssm, "conv_x": conv_x, "conv_b": conv_b,
                 "conv_c": conv_c}
    return out, new_cache


def mamba_cache_defs(cfg: ModelConfig, batch: int, n_layers: int,
                     dtype) -> Dict[str, Any]:
    """(shape, dtype, logical_axes) per cache leaf, layer-stacked."""
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    h = d_inner // s.head_dim
    w = s.conv_width - 1
    return {
        "ssm": ((n_layers, batch, h, s.state_dim, s.head_dim), jnp.float32,
                ("layers", "batch", "ssm_heads", "ssm_state", "head_dim")),
        "conv_x": ((n_layers, batch, w, d_inner), dtype,
                   ("layers", "batch", "conv", "mlp")),
        "conv_b": ((n_layers, batch, w, s.state_dim), dtype,
                   ("layers", "batch", "conv", "ssm_state")),
        "conv_c": ((n_layers, batch, w, s.state_dim), dtype,
                   ("layers", "batch", "conv", "ssm_state")),
    }


# ---------------------------------------------------------------------------
# attention-free SSM LM (mamba2-2.7b)
# ---------------------------------------------------------------------------

class SSMModel:
    def __init__(self, cfg: ModelConfig, *, scan_layers: bool = True,
                 remat: str = "none", attn_impl: str = "jnp"):
        self.cfg = cfg
        self.scan_layers = scan_layers
        self.remat = remat

    def param_defs(self) -> L.ParamDefs:
        cfg = self.cfg
        block = {
            "ln": L.norm_defs(cfg.d_model, cfg.norm_type),
            "mamba": mamba_defs(cfg),
        }
        defs = {
            "embed": L.embed_defs(cfg.vocab_size, cfg.d_model),
            "layers": L.stack_defs(block, cfg.n_layers),
            "final_norm": L.norm_defs(cfg.d_model, cfg.norm_type),
        }
        defs.update(L.unembed_defs(cfg.vocab_size, cfg.d_model,
                                   cfg.tie_embeddings))
        return defs

    def init(self, key: jax.Array):
        return L.init_params(self.param_defs(), key,
                             dtype=jnp.dtype(self.cfg.param_dtype))

    def _block(self, lp, x, return_state: bool):
        cfg = self.cfg
        h = L.apply_norm(lp["ln"], x, cfg.norm_type, cfg.norm_eps)
        out = mamba_fwd(lp["mamba"], h, cfg, return_state=return_state)
        if return_state:
            out, tails = out
            return x + out, tails
        return x + out

    def backbone(self, params, x, return_cache: bool = False):
        cfg = self.cfg

        def scan_body(carry, lp):
            if return_cache:
                x, tails = self._block(lp, carry, True)
                return x, tails
            fn = lambda c, p: self._block(p, c, False)
            if self.remat != "none":
                fn = jax.checkpoint(fn)
            return fn(carry, lp), None

        if self.scan_layers:
            with SSD.repeated(cfg.n_layers):
                x, ys = _scan(scan_body, x, params["layers"])
        else:
            ys_list = []
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda p: p[i], params["layers"])
                x, y = scan_body(x, lp)
                ys_list.append(y)
            ys = (jax.tree.map(lambda *zs: jnp.stack(zs), *ys_list)
                  if return_cache else None)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        return (x, ys) if return_cache else x

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
        defs = mamba_cache_defs(self.cfg, batch_size, self.cfg.n_layers, dtype)
        return {k: jnp.zeros(shape, dt) for k, (shape, dt, _) in defs.items()}

    def decode_step(self, params, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(params["embed"], batch["token"], dtype)
        cache = batch["cache"]

        def scan_body(x, layer_in):
            lp, c = layer_in
            h = L.apply_norm(lp["ln"], x, cfg.norm_type, cfg.norm_eps)
            out, nc = mamba_decode_step(lp["mamba"], h, c, cfg)
            return x + out, nc

        x, new_cache = _scan(scan_body, x, (params["layers"], cache))
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        return self._logits_last(params, x[:, -1]), new_cache

    def input_layout(self, kind: str, batch: int, seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        if kind == "train":
            return {
                "tokens": ((batch, seq), jnp.int32, ("batch", "seq")),
                "targets": ((batch, seq), jnp.int32, ("batch", "seq")),
            }
        if kind == "prefill":
            return {"tokens": ((batch, seq), jnp.int32, ("batch", "seq"))}
        if kind == "decode":
            # NOTE: SSM cache is O(1) in seq — `seq` is ignored by layout
            cache = mamba_cache_defs(cfg, batch, cfg.n_layers,
                                     jnp.dtype(cfg.dtype))
            return {
                "token": ((batch, 1), jnp.int32, ("batch", "seq")),
                "cache": cache,
                "index": ((), jnp.int32, ()),
            }
        raise ValueError(kind)
