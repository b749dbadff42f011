"""Shape-agnostic wrappers: flatten → (m, 128) lane tiles → kernel."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.quant.kernel import dequantize_padded, quantize_padded

_LANE = 128


def _to_tiles(flat: jax.Array) -> Tuple[jax.Array, int]:
    n = flat.shape[0]
    m = -(-n // _LANE)
    m8 = -(-m // 8) * 8                      # sublane alignment
    padded = jnp.zeros((m8 * _LANE,), flat.dtype).at[:n].set(flat)
    return padded.reshape(m8, _LANE), n


@auto_interpret
@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x: jax.Array, *, interpret: bool
             ) -> Tuple[jax.Array, jax.Array]:
    """Any-shape fp tensor → (q int8 same shape, scale f32 scalar)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    tiles, n = _to_tiles(x32.reshape(-1))
    block_m = min(tiles.shape[0], 512)
    # pad rows to a block multiple
    m = tiles.shape[0]
    mpad = -(-m // block_m) * block_m
    if mpad != m:
        tiles = jnp.zeros((mpad, _LANE), tiles.dtype).at[:m].set(tiles)
    q = quantize_padded(tiles, scale.reshape(1, 1), block_m=block_m,
                        interpret=interpret)
    return q.reshape(-1)[:n].reshape(x.shape), scale


@auto_interpret
@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize(q: jax.Array, scale: jax.Array, *,
               interpret: bool) -> jax.Array:
    tiles, n = _to_tiles(q.reshape(-1))
    block_m = min(tiles.shape[0], 512)
    m = tiles.shape[0]
    mpad = -(-m // block_m) * block_m
    if mpad != m:
        tiles = jnp.zeros((mpad, _LANE), tiles.dtype).at[:m].set(tiles)
    x = dequantize_padded(tiles.astype(jnp.int8), scale.reshape(1, 1),
                          block_m=block_m, interpret=interpret)
    return x.reshape(-1)[:n].reshape(q.shape)
