"""Public SSD wrapper: sequence padding + chunk-size selection.

Padding is safe because a padded step with Δ = 0 is the identity: the decay
``exp(0·A) = 1`` leaves the state untouched and the injected term is 0; the
padded y rows are sliced off.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.ssd.kernel import ssd_scan_padded


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@auto_interpret
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
             cm: jax.Array, *, chunk: int = 128,
             interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for :func:`repro.kernels.ssd.ref.ssd_scan` (zero init state)."""
    b, l, h, p = x.shape
    lp = _round_up(l, chunk)
    if lp != l:
        pad = [(0, 0), (0, lp - l)]
        x = jnp.pad(x, pad + [(0, 0), (0, 0)])
        dt = jnp.pad(dt, pad + [(0, 0)])
        bm = jnp.pad(bm, pad + [(0, 0)])
        cm = jnp.pad(cm, pad + [(0, 0)])
    y, sfin = ssd_scan_padded(x, dt, a, bm, cm, chunk=chunk,
                              interpret=interpret)
    return y[:, :l], sfin
