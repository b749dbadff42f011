"""Shared building blocks: param-def system, norms, RoPE, embeddings, MLPs.

Parameters are plain pytrees (nested dicts of ``jnp.ndarray``). Every leaf is
declared through a :class:`Param` so the matching *logical sharding axes*
tree can be derived mechanically (``axes_of``) and stays in sync with shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.telemetry import scope
from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class Param:
    """Declaration of one parameter leaf."""

    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | fan_in
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


ParamDefs = Dict[str, Any]  # nested dict of Param


def _init_leaf(p: Param, key, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "ssm_a":
        # Mamba2 A init: A = −exp(a_log) spread over [1, 16]
        h = p.shape[-1]
        return jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, h)), p.shape).astype(dtype)
    if p.init == "fan_in":
        import math
        fan_in = p.shape[0] if len(p.shape) == 1 else math.prod(p.shape[:-1])
        scale = 1.0 / max(1.0, fan_in) ** 0.5
        return (jax.random.normal(key, p.shape) * scale).astype(dtype)
    return (jax.random.normal(key, p.shape) * p.scale).astype(dtype)


def init_params(defs: ParamDefs, key: jax.Array, dtype=jnp.float32):
    """Materialize a param pytree from defs; deterministic per-leaf keys."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, Param))
    keys = jax.random.split(key, max(1, len(leaves)))
    arrs = [_init_leaf(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrs)


def axes_of(defs: ParamDefs):
    """Logical-axes pytree matching ``init_params`` output."""
    return jax.tree.map(lambda p: p.logical, defs,
                        is_leaf=lambda x: isinstance(x, Param))


def shapes_of(defs: ParamDefs):
    return jax.tree.map(lambda p: p.shape, defs,
                        is_leaf=lambda x: isinstance(x, Param))


def stack_defs(defs: ParamDefs, n: int) -> ParamDefs:
    """Prepend a scanned ``layers`` dim of size n to every leaf."""
    return jax.tree.map(
        lambda p: Param((n,) + p.shape, ("layers",) + p.logical, p.init, p.scale),
        defs, is_leaf=lambda x: isinstance(x, Param))


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def rms_norm_defs(d: int) -> Param:
    return Param((d,), ("embed",), init="ones")


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def norm_defs(d: int, norm_type: str = "rms") -> ParamDefs:
    if norm_type == "layer":
        return {"scale": Param((d,), ("embed",), init="ones"),
                "bias": Param((d,), ("embed",), init="zeros")}
    return {"scale": Param((d,), ("embed",), init="ones")}


def apply_norm(params, x: jax.Array, norm_type: str, eps: float) -> jax.Array:
    if norm_type == "layer":
        return layer_norm(x, params["scale"], params["bias"], eps)
    return rms_norm(x, params["scale"], eps)


def rotary_cos_sin(positions: jax.Array, head_dim: int, theta: float,
                   dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int32 → cos/sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); cos/sin: (..., seq, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def embed_defs(vocab: int, d_model: int) -> ParamDefs:
    return {"embedding": Param((vocab, d_model), ("vocab", "embed"))}


def embed(params, tokens: jax.Array, dtype) -> jax.Array:
    """Token embedding lookup.

    Three paths. XLA's SPMD gather partitioning CHECK-crashes when a
    sharded-table gather sits inside a manual (pod) subgroup at 512
    devices, so on a real mesh we never hand the partitioner a gather:

    * big T  → manual vocab-parallel lookup (Megatron-style masked local
      gather + ``psum_scatter`` over the vocab axis, emitting the
      act_seq-sharded layout directly);
    * small T (decode) → one-hot einsum (gather-free, partitions like any
      matmul; flops negligible at decode scale);
    * no mesh (CPU tests) → plain gather.
    """
    from repro.sharding import current_rules

    table = params["embedding"]
    v, d = table.shape
    b, s = tokens.shape
    rules = current_rules()
    if rules is not None and rules.mesh is not None:
        mesh = rules.mesh
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        v_ax = rules.mesh_axes_for("vocab")
        d_ax = rules.mesh_axes_for("embed")
        if (b * s >= 32768 and len(v_ax) == 1
                and v % sizes[v_ax[0]] == 0 and s % sizes[v_ax[0]] == 0
                and b % (sizes[d_ax[0]] if d_ax else 1) == 0
                and (not d_ax or d % sizes[d_ax[0]] == 0)):
            return _embed_sharded(table, tokens, dtype, mesh,
                                  d_ax[0] if d_ax else None, v_ax[0])
        oh = jax.nn.one_hot(tokens, v, dtype=dtype)
        out = jnp.einsum("bsv,vd->bsd", oh, table.astype(dtype))
        return constrain(out, "batch", "act_seq", "embed")
    out = table.astype(dtype)[tokens]
    return constrain(out, "batch", "act_seq", "embed")


def _embed_sharded(table: jax.Array, tokens: jax.Array, dtype, mesh,
                   data_axis, model_axis) -> jax.Array:
    """Manual vocab-parallel embedding under full-manual shard_map."""
    from jax.sharding import PartitionSpec as P

    def body(tok, tab):
        # tok: (B_loc, S) · tab: (V_loc, D_loc)
        if data_axis is not None:
            tab = jax.lax.all_gather(tab, data_axis, axis=1, tiled=True)
        v_loc = tab.shape[0]
        lo = jax.lax.axis_index(model_axis) * v_loc
        ids = tok - lo
        ok = (ids >= 0) & (ids < v_loc)
        x = tab[jnp.clip(ids, 0, v_loc - 1)].astype(jnp.float32)
        x = jnp.where(ok[..., None], x, 0.0)
        # sum the per-vocab-shard partials, scattering seq → act_seq layout.
        # f32 payload: XLA's bf16 AllReducePromotion pass CHECK-crashes on
        # cross-pod bf16 reductions (same bug as the flash-decode merge).
        x = jax.lax.psum_scatter(x, model_axis, scatter_dimension=1,
                                 tiled=True)
        return x.astype(dtype)

    axes = {model_axis} | ({data_axis} if data_axis else set())
    tok_spec = P(data_axis, None) if data_axis else P(None, None)
    tab_spec = P(model_axis, data_axis)
    out_spec = P(data_axis, model_axis, None)
    # mesh=None → use the context mesh: inside an outer (pod-manual)
    # shard_map the context is an AbstractMesh with pod already Manual,
    # and passing the concrete mesh is rejected
    fn = jax.shard_map(body, in_specs=(tok_spec, tab_spec),
                       out_specs=out_spec, axis_names=axes, check_vma=False)
    out = fn(tokens, table)
    return constrain(out, "batch", "act_seq", "embed")


def unembed(params, x: jax.Array, tied: bool) -> jax.Array:
    table = params["embedding"] if tied else params["out_embedding"]
    logits = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))
    return constrain(logits, "batch", "seq", "vocab")


def unembed_defs(vocab: int, d_model: int, tied: bool) -> ParamDefs:
    if tied:
        return {}
    return {"out_embedding": Param((vocab, d_model), ("vocab", "embed"))}


# ---------------------------------------------------------------------------
# dense (SwiGLU) MLP
# ---------------------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int) -> ParamDefs:
    return {
        "w_gate": Param((d_model, d_ff), ("embed", "mlp"), init="fan_in"),
        "w_up": Param((d_model, d_ff), ("embed", "mlp"), init="fan_in"),
        "w_down": Param((d_ff, d_model), ("mlp", "embed"), init="fan_in"),
    }


def mlp(params, x: jax.Array) -> jax.Array:
    dtype = x.dtype
    with scope("lm.mlp"):
        gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(dtype))
        up = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(dtype))
        h = jax.nn.silu(gate) * up
        h = constrain(h, "batch", "seq", "mlp")
        out = jnp.einsum("bsf,fd->bsd", h, params["w_down"].astype(dtype))
        return constrain(out, "batch", "act_seq", "embed")
