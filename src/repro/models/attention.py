"""Attention: GQA/MQA/MHA, RoPE, prefix/causal masks, KV-cache decode.

Two distribution regimes:

* **train / prefill** — full-sequence attention; activations sharded
  ``batch→data, heads→model`` via logical constraints. Causal
  self-attention at a lane-aligned length on TPU devices, where the mesh
  gives each chip a batch or head shard of its own, runs the fused flash
  kernel with its backward (splash attention, :func:`_sdpa_flash`);
  everything else runs the jnp paths (:func:`_sdpa_jnp`, and
  :func:`_sdpa_chunked_jnp` from ``_CHUNK_THRESHOLD``). ``impl="pallas"``
  selects the forward-only Pallas kernel.
* **decode** — the KV cache is sharded along *sequence* over the model axis
  (``cache_seq`` rule). A partial-manual ``shard_map`` computes blockwise
  attention per shard and merges with a log-sum-exp ``psum`` — a distributed
  flash-decode. This is what makes 500k-token caches fit (and is the SP
  scheme the hybrid archs use at ``long_500k``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro import flags as _flags, kernels as _kernels
from repro.config.base import ModelConfig
from repro.core.telemetry import ATTENTION, scope
from repro.models import layers as L
from repro.sharding import constrain, current_rules


def _scan(*args, **kw):
    kw.setdefault("unroll", _flags.scan_unroll_arg())
    return jax.lax.scan(*args, **kw)

def attn_defs(cfg: ModelConfig, d_model: Optional[int] = None) -> L.ParamDefs:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    defs: L.ParamDefs = {
        "wq": L.Param((d, cfg.n_heads, hd), ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": L.Param((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wv": L.Param((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wo": L.Param((cfg.n_heads, hd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if cfg.qkv_bias:
        defs["bq"] = L.Param((cfg.n_heads, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = L.Param((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = L.Param((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
    return defs


def _project_qkv(params, x, kv_x, cfg: ModelConfig):
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def make_mask(q_len: int, kv_len: int, mode: str, prefix_len: int = 0,
              q_offset: int = 0) -> Optional[jax.Array]:
    """Boolean (q_len, kv_len) mask; True = attend. ``mode``: causal|prefix|full."""
    if mode == "full":
        return None
    rows = jnp.arange(q_len)[:, None] + q_offset
    cols = jnp.arange(kv_len)[None, :]
    causal = cols <= rows
    if mode == "causal":
        return causal
    if mode == "prefix":
        return causal | (cols < prefix_len)
    raise ValueError(mode)


def _scaled(scores: jax.Array, hd: int, scale: Optional[float]):
    """Scores times the softmax scale ``scale``, or divided by sqrt(hd)
    where it is None."""
    return scores / (hd ** 0.5) if scale is None else scores * scale


def _sdpa_jnp(q, k, v, mask, scale: Optional[float] = None) -> jax.Array:
    """Grouped-query scaled-dot-product attention, jnp reference.

    q: (B,S,H,hd) · k/v: (B,T,KV,hd) → (B,S,H,hd). H = KV·G. ``scale``
    multiplies the scores (None: 1/sqrt(hd)).
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    q = q.reshape(b, s, kv, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = _scaled(scores, hd, scale)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, hd)


# seq length at/beyond which the q-chunked path replaces full-score SDPA
# (the (B,H,S,S) score tensor at 4k is already GBs/device when an arch's
# head count doesn't divide the model axis and falls back to replication;
# chunking caps scores at (B,H,Q_CHUNK,S) per scan step)
_CHUNK_THRESHOLD = 2048
_Q_CHUNK = 512


def _sdpa_chunked_jnp(q, k, v, mask_mode: str, prefix_len: int,
                      q_chunk: int = _Q_CHUNK,
                      scale: Optional[float] = None) -> jax.Array:
    """Query-chunked SDPA: lax.scan over q blocks, full softmax row per
    block (f32). Scores live at (B,H,q_chunk,T) per step — O(S) not O(S²)
    memory. XLA-lowerable twin of the Pallas flash kernel.

    Head-sharding strategy (the score tensors dominate attention memory
    and compute placement):

    * grouped (B,KV,G,·,·) layout when KV or G divides the model axis
      (qwen3 G=16, zamba KV=32) — keeps GQA's KV bandwidth advantage;
    * flat-head (B,H,·,·) layout with KV broadcast to H when only the
      flat head count divides (phi3.5 H=32 KV=8 G=4, internlm, qwen2.5) —
      XLA cannot shard a dim split across two factors, so the grouped
      layout would replicate or gather here;
    * otherwise (llama 24H, smollm 15H, whisper/paligemma 8H) nothing
      head-like divides: scores replicate across the model axis unless
      the ``attn_q_seq`` rule (context-parallel attention, a §Perf lever)
      shards the q-chunk dim instead.
    """
    from repro.sharding import current_rules

    b, s, h, hd = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    g = h // kv
    if s % q_chunk != 0:
        return _sdpa_jnp(q, k, v, make_mask(s, t, mask_mode, prefix_len),
                         scale)
    nq = s // q_chunk
    cols = jnp.arange(t)[None, :]

    rules = current_rules()
    on_mesh = rules is not None and rules.mesh is not None
    flat_heads = (on_mesh and rules.would_shard("heads", h)
                  and not rules.would_shard("kv_heads", kv)
                  and not rules.would_shard("q_group", g))
    # context-parallel fallback: when NO head-like dim divides the model
    # axis (llama 24H, smollm 15H, whisper/paligemma 8H on a 16-wide
    # axis), shard the q-chunk rows over it instead (act_seq) — otherwise
    # scores replicate 16× in both FLOPs and HBM traffic (§Perf cell A:
    # 7.4× memory-term win). "attn_q_seq" stays as an explicit override.
    q_axis = "attn_q_seq"
    if (on_mesh and not flat_heads and not rules.would_shard("heads", h)
            and not rules.would_shard("kv_heads", kv)
            and not rules.would_shard("q_group", g)
            and not rules.mesh_axes_for("attn_q_seq")):
        q_axis = "act_seq"

    def _mask(scores, iq, extra_dims):
        if mask_mode == "full":
            return scores
        rows = iq * q_chunk + jnp.arange(q_chunk)[:, None]
        m = cols <= rows
        if mask_mode == "prefix":
            m = m | (cols < prefix_len)
        return jnp.where(m[(None,) * extra_dims], scores, -1e30)

    if flat_heads:
        kr = jnp.repeat(k, g, axis=2)       # (B,T,H,hd) — slices of the
        vr = jnp.repeat(v, g, axis=2)       # replicated KV, H-sharded
        kr = constrain(kr, "batch", "seq", "heads", "head_dim")
        vr = constrain(vr, "batch", "seq", "heads", "head_dim")
        qf = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)

        @jax.checkpoint
        def block_flat(carry, inp):
            qc, iq = inp                                 # (B,Qc,H,hd)
            qc = constrain(qc, "batch", "attn_q_seq", "heads", "head_dim")
            scores = _scaled(jnp.einsum("bshd,bthd->bhst", qc,
                                        kr).astype(jnp.float32), hd, scale)
            scores = _mask(scores, iq, 2)
            scores = constrain(scores, "batch", "heads", "attn_q_seq", None)
            probs = jax.nn.softmax(scores, axis=-1).astype(qc.dtype)
            o = jnp.einsum("bhst,bthd->bshd", probs, vr)
            o = constrain(o, "batch", "attn_q_seq", "heads", "head_dim")
            return carry, o

        _, outs = _scan(block_flat, (), (qf, jnp.arange(nq)))
        return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)

    qg = q.reshape(b, nq, q_chunk, kv, g, hd).transpose(1, 0, 2, 3, 4, 5)

    # checkpointed: without this the scan backward stacks every chunk's
    # scores/probs (≈ the full (B,H,S,S) tensor again); with it the bwd
    # recomputes one chunk at a time — the flash-attention memory profile
    @jax.checkpoint
    def block(carry, inp):
        qc, iq = inp                                     # (B,Qc,KV,G,hd)
        qc = constrain(qc, "batch", q_axis, "kv_heads", "q_group",
                       "head_dim")
        scores = jnp.einsum("bskgd,btkd->bkgst", qc, k).astype(jnp.float32)
        scores = _scaled(scores, hd, scale)
        scores = _mask(scores, iq, 3)
        scores = constrain(scores, "batch", "kv_heads", "q_group",
                           q_axis, None)
        probs = jax.nn.softmax(scores, axis=-1).astype(qc.dtype)
        o = jnp.einsum("bkgst,btkd->bskgd", probs, v)
        o = constrain(o, "batch", q_axis, "kv_heads", "q_group",
                      "head_dim")
        return carry, o.reshape(b, q_chunk, h, hd)

    _, outs = _scan(block, (), (qg, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


# the flash kernel's shapes: a sequence of whole 128-lane tiles, from 512
# on (below it the jnp path was faster on a v5e, PERF.md); a head no wider
# than the kernel's VMEM tiles allow
_FLASH_MIN_SEQ = 512
_FLASH_MAX_HEAD_DIM = 256


def _on_tpu() -> bool:
    """Whether the devices the computation is placed on are TPUs: the
    current rules' mesh if there is one, else the process' devices."""
    rules = current_rules()
    mesh = rules.mesh if rules is not None else None
    devices = mesh.devices.flat if mesh is not None else jax.devices()
    return all(d.platform == "tpu" for d in devices)


def _attention_path(q_shape: Tuple[int, ...], kv_heads: int,
                    mask_mode: str, cross: bool) -> str:
    """``flash`` for causal self-attention at a lane-aligned length on
    TPUs, where each chip's kernel call gets a shard of its own
    (:func:`_flash_spec`); else ``chunked`` from ``_CHUNK_THRESHOLD`` on,
    ``full`` below."""
    b, seq_len, h, head_dim = q_shape
    if (mask_mode == "causal" and not cross and seq_len % 128 == 0
            and seq_len >= _FLASH_MIN_SEQ and head_dim <= _FLASH_MAX_HEAD_DIM
            and _on_tpu() and _flash_spec(b, h, kv_heads) is not None):
        return "flash"
    return "chunked" if seq_len >= _CHUNK_THRESHOLD else "full"


@functools.lru_cache(maxsize=None)
def _splash_plan(seq_len: int, group: int):
    """The causal mask over ``group`` query heads and the block sizes of
    the splash kernel at ``seq_len``: q and kv blocks of the largest of
    1024, 512, 256, 128 dividing ``seq_len``, kv computed 512 at a time,
    and the fused dq/dkv backward (the fastest of the settings compared
    on a v5e, PERF.md). Splash caches a mask's block tables by these
    objects, so the kernel each trace makes from them reuses the tables;
    fully masked blocks are skipped in both passes."""
    blk = next(b for b in (1024, 512, 256, 128) if seq_len % b == 0)
    sizes = splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=min(blk, 512),
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        use_fused_bwd_kernel=True)
    mask = splash.MultiHeadMask(
        [splash.CausalMask((seq_len, seq_len))] * group)
    return mask, sizes


def _flash_spec(b: int, h: int, kv: int) -> Optional[P]:
    """The (B, S, heads, hd) spec of each chip's kernel call under the
    current rules' mesh (``P()`` with none): batch over its mesh axes,
    heads over theirs where whole KV groups divide them. ``None`` where a
    mesh axis of more than one device, not manual already, carries
    neither: the compiler cannot split the kernel, so every chip on that
    axis would run the same attention. The jnp paths split it there
    (flat heads, or the q rows over ``act_seq``)."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return P()
    groups = (rules.would_shard("kv_heads", kv)
              and rules.would_shard("heads", h)
              and rules.mesh_axes_for("kv_heads")
              == rules.mesh_axes_for("heads"))
    spec = rules.spec_for(("batch", None, "kv_heads" if groups else None),
                          (b, 1, kv))
    split = set(jax.sharding.get_abstract_mesh().manual_axes)
    for entry in spec:
        split.update((entry,) if isinstance(entry, str) else entry or ())
    if any(n > 1 and a not in split for a, n in rules.mesh.shape.items()):
        return None
    return spec


def _sdpa_flash(q, k, v, interpret: Optional[bool] = None,
                scale: Optional[float] = None) -> jax.Array:
    """Causal GQA attention through the fused splash kernel.

    q: (B,S,H,hd) · k/v: (B,S,KV,hd) → (B,S,H,hd). The kernel does not
    scale its scores: q is multiplied by ``scale`` (None: 1/sqrt(hd))
    first, in its own dtype. One vmap over the
    B·KV (batch, KV head) pairs, each call attending G = H/KV query heads
    to one shared KV head (never repeated); a vmap over batch and another
    over KV heads left ≈ 10 ms more copy waits in each step on a v5e
    (PERF.md). bf16 operands feed the matrix unit with float32
    accumulation and a float32 softmax. On a mesh the call is manual
    over every axis (``shard_map``): the compiler cannot partition the
    kernel, so each chip runs it on its own batch and head shard.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    if interpret is None:
        interpret = _kernels.default_interpret()
    mask, sizes = _splash_plan(s, g)
    kernel = splash.make_splash_mqa_single_device(
        mask, block_sizes=sizes, interpret=interpret)
    q = q * jnp.asarray(hd ** -0.5 if scale is None else scale, q.dtype)

    def attend(q, k, v):
        bl, kvl = q.shape[0], k.shape[2]
        n = bl * kvl

        def by_kv_head(x):                  # (B,S,KV,hd) → (B·KV,S,hd)
            return x.transpose(0, 2, 1, 3).reshape(n, s, hd)

        qg = q.reshape(bl, s, kvl, g, hd).transpose(0, 2, 3, 1, 4)
        o = jax.vmap(kernel)(qg.reshape(n, g, s, hd), by_kv_head(k),
                             by_kv_head(v))
        o = o.reshape(bl, kvl, g, s, hd).transpose(0, 3, 1, 2, 4)
        return o.reshape(bl, s, kvl * g, hd)

    rules = current_rules()
    if rules is None or rules.mesh is None:
        return attend(q, k, v)
    spec = _flash_spec(b, h, kv)
    context = jax.sharding.get_abstract_mesh()
    # under the local-SGD block the replica axis is manual already
    manual = set(rules.mesh.axis_names) - set(context.manual_axes)
    return jax.shard_map(attend, mesh=rules.mesh if context.empty else None,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=manual, check_vma=False)(q, k, v)


def full_attention(params, x: jax.Array, positions: jax.Array, cfg: ModelConfig,
                   mask_mode: str = "causal", prefix_len: int = 0,
                   kv_x: Optional[jax.Array] = None,
                   kv_positions: Optional[jax.Array] = None,
                   impl: str = "jnp", return_kv: bool = False):
    """Training / prefill attention over a full sequence (optionally cross).

    ``return_kv=True`` also returns the (post-RoPE) k, v — the prefill path
    stores them directly as the decode cache. The config's
    ``position_embedding`` ("nope": no RoPE) and ``attention_multiplier``
    (the softmax scale, 1/sqrt(hd) at 0) hold on every path.
    """
    scale = cfg.attention_multiplier or None
    with scope("lm.attention"):
        q, k, v = _project_qkv(params, x, kv_x, cfg)
        # no RoPE across enc-dec cross attention
        use_rope = kv_x is None and cfg.position_embedding == "rope"
        if use_rope:
            cos, sin = rotary_cos_sin(positions, cfg)
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        path = ("pallas" if impl == "pallas" else _attention_path(
            q.shape, k.shape[2], mask_mode, cross=kv_x is not None))
        ATTENTION.add(path)
        if path == "pallas":
            if scale is not None:
                raise ValueError("the forward-only Pallas kernel scales "
                                 "by 1/sqrt(head_dim) only")
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(
                q, k, v, causal=(mask_mode == "causal"),
                prefix_len=prefix_len if mask_mode == "prefix" else 0)
        elif path == "flash":
            out = _sdpa_flash(q, k, v, scale=scale)
        elif path == "chunked":
            out = _sdpa_chunked_jnp(q, k, v, mask_mode, prefix_len,
                                    scale=scale)
        else:
            mask = make_mask(q.shape[1], k.shape[1], mask_mode, prefix_len)
            out = _sdpa_jnp(q, k, v, mask, scale)
        out = constrain(out, "batch", "seq", "heads", "head_dim")
        y = jnp.einsum("bshd,hdm->bsm", out, params["wo"].astype(x.dtype))
        y = constrain(y, "batch", "act_seq", "embed")
        if return_kv:
            return y, k, v
        return y


def rotary_cos_sin(positions, cfg: ModelConfig):
    return L.rotary_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    hd = cfg.resolved_head_dim
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes() -> Tuple[str, ...]:
    return ("layers", "batch", "cache_seq", "kv_heads", "head_dim")


def _decode_attn_chunk(q, k_chunk, v_chunk, index, chunk_offset,
                       scale: Optional[float] = None):
    """Per-shard flash-decode partial: returns (o, l, m) to be lse-merged.

    q: (B,1,KV,G,hd) · k/v_chunk: (B,Sc,KV,hd); positions chunk_offset+i
    valid iff <= index.
    """
    sc = k_chunk.shape[1]
    scores = jnp.einsum("bqkgd,btkd->bkgqt", q, k_chunk).astype(jnp.float32)
    scores = _scaled(scores, q.shape[-1], scale)
    pos = chunk_offset + jnp.arange(sc)
    valid = pos <= index
    scores = jnp.where(valid[None, None, None, None, :], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe)
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqt,btkd->bkgqd", p.astype(v_chunk.dtype), v_chunk)
    return o, l, m_safe, jnp.isfinite(m)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     index: jax.Array, mesh=None, seq_shard_axis: str = "model",
                     scale: Optional[float] = None) -> jax.Array:
    """One-token attention against a sequence-sharded cache.

    q: (B,1,H,hd); k/v_cache: (B,S,KV,hd) sharded (data, model, -, -).
    Merges per-shard partials with an lse-combine over ``seq_shard_axis``.
    Falls back to single-shard math when no mesh/axis available.
    """
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    qg = q.reshape(b, 1, kv, g, hd)

    rules = current_rules()
    mesh = mesh or (rules.mesh if rules else None)
    n_shards = (dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        seq_shard_axis, 1) if mesh is not None else 1)
    s_total = k_cache.shape[1]
    if mesh is None or n_shards <= 1 or s_total % n_shards != 0:
        # single-shard math (no mesh, or a cache length that doesn't tile
        # the model axis, e.g. whisper's 1500-frame cross cache)
        o, l, m, has = _decode_attn_chunk(qg, k_cache, v_cache, index, 0,
                                          scale)
        out = (o / jnp.maximum(l, 1e-30)).astype(q.dtype)
        return out.reshape(b, 1, h, hd)

    chunk = s_total // n_shards

    def shard_fn(qg, k_chunk, v_chunk, index):
        shard_id = jax.lax.axis_index(seq_shard_axis)
        o, l, m, _ = _decode_attn_chunk(qg, k_chunk, v_chunk, index,
                                        shard_id * chunk, scale)
        # lse merge across shards — all-reduce payloads kept f32 (XLA's
        # bf16 AllReducePromotion pass CHECK-crashes on these ARs)
        m_glob = jax.lax.pmax(m, seq_shard_axis)
        rescale = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * rescale, seq_shard_axis)
        o_glob = jax.lax.psum(o.astype(jnp.float32) * rescale,
                              seq_shard_axis)
        return (o_glob / jnp.maximum(l_glob, 1e-30)).astype(qg.dtype)

    fn = jax.shard_map(
        shard_fn,                   # context mesh (nests under pod-manual)
        in_specs=(P(), P(None, seq_shard_axis), P(None, seq_shard_axis), P()),
        out_specs=P(),
        check_vma=False, axis_names={seq_shard_axis})
    out = fn(qg, k_cache, v_cache, index)
    return out.reshape(b, 1, h, hd)


def decode_step_attention(params, x: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, index: jax.Array,
                          cfg: ModelConfig,
                          cross: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token attention step; returns (y, new_k, new_v).

    x: (B,1,d). cache_k/v: (B,S,KV,hd). ``cross=True`` skips cache update &
    RoPE (whisper cross-attention against fixed encoder states).
    """
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
    if not cross:
        k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
        v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
        if "bk" in params:
            k_new = k_new + params["bk"].astype(dtype)
            v_new = v_new + params["bv"].astype(dtype)
        if cfg.position_embedding == "rope":
            pos = jnp.full((x.shape[0], 1), index, jnp.int32)
            cos, sin = rotary_cos_sin(pos, cfg)
            q = L.apply_rope(q, cos, sin)
            k_new = L.apply_rope(k_new, cos, sin)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new.astype(cache_k.dtype), index, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new.astype(cache_v.dtype), index, axis=1)
        eff_index = index
    else:
        eff_index = cache_k.shape[1] - 1  # attend over the whole encoder output
    out = decode_attention(q, cache_k, cache_v, eff_index,
                           scale=cfg.attention_multiplier or None)
    y = jnp.einsum("bshd,hdm->bsm", out, params["wo"].astype(dtype))
    return constrain(y, "batch", "seq", "embed"), cache_k, cache_v
