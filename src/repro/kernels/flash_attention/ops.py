"""Public flash-attention wrapper: layout + padding glue.

Model code uses (B, S, H, dh) activations; the kernel wants (B, H, S, dh)
and block-aligned S / lane-aligned dh. Sequence padding is masked out by
causality for queries (extra rows are discarded) and by explicit key
validity for keys (padded keys land in masked-out positions only when the
caller guarantees ``sk`` alignment — ops pads ``sk`` and relies on the
causal/prefix mask plus a validity clamp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.flash_attention.kernel import flash_attention_padded

_LANE = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@auto_interpret
@functools.partial(jax.jit, static_argnames=(
    "causal", "prefix_len", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, prefix_len: int = 0,
                    block_q: int = 0, block_k: int = 0,
                    interpret: bool) -> jax.Array:
    """q: (B, S, H, dh) · k/v: (B, S, KV, dh) → (B, S, H, dh)."""
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]

    bq = block_q or min(512, _round_up(sq, 128))
    bk = block_k or min(512, _round_up(sk, 128))
    sqp = _round_up(sq, bq)
    skp = _round_up(sk, bk)
    dhp = _round_up(dh, _LANE)

    qt = jnp.zeros((b, h, sqp, dhp), q.dtype).at[:, :, :sq, :dh].set(
        q.transpose(0, 2, 1, 3))
    kt = jnp.zeros((b, kvh, skp, dhp), k.dtype).at[:, :, :sk, :dh].set(
        k.transpose(0, 2, 1, 3))
    vt = jnp.zeros((b, kvh, skp, dhp), v.dtype).at[:, :, :sk, :dh].set(
        v.transpose(0, 2, 1, 3))
    if skp != sk and not causal:
        # full attention with padded keys: restrict to the valid prefix
        # (kernel's non-causal prefix mode masks cols ≥ prefix_len)
        prefix_len = sk
    out = flash_attention_padded(qt, kt, vt, causal=causal,
                                 prefix_len=prefix_len, block_q=bq,
                                 block_k=bk, sm_scale=1.0 / (dh ** 0.5),
                                 interpret=interpret)
    return out[:, :, :sq, :dh].transpose(0, 2, 1, 3)
