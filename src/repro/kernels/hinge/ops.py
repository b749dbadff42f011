"""Public wrapper: padding/alignment glue around the hinge Pallas kernel.

Pads d to a lane multiple (128) and n to a block multiple. Padded rows get
y = 0 so their hinge contribution vanishes (y multiplies every term);
padded feature columns are zero in both X and w so they contribute nothing
to margins and stay zero in the gradient.

``interpret`` defaults to the package rule
(:func:`repro.kernels.default_interpret`): compiled Pallas on TPU/GPU
backends, the interpreter only on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.hinge.kernel import hinge_block_grad_padded

_LANE = 128
# X row-block budget. The f32 (HIGHEST) dots keep several copies of the
# block in VMEM and the pipeline double-buffers it: a 4 MiB block at
# d=2000 needs 16.9 MiB of scoped VMEM on a v5e, over its 16 MiB limit.
_X_BLOCK_BYTES = 2 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@auto_interpret
@functools.partial(jax.jit, static_argnames=("c", "block_n", "interpret"))
def hinge_block_grad(w: jax.Array, x: jax.Array, y: jax.Array, c: float = 1.0,
                     *, block_n: int = 0, interpret: bool) -> jax.Array:
    """Drop-in for :func:`repro.kernels.hinge.ref.hinge_block_grad`."""
    n, d = x.shape
    dp = _round_up(d, _LANE)
    if block_n <= 0:
        # one sublane-aligned block if the rows fit, else a lane-aligned
        # one (y's row-block is its last dim) within the VMEM budget
        fit = _X_BLOCK_BYTES // (dp * x.dtype.itemsize) // _LANE * _LANE
        block_n = min(max(_LANE, fit), 512, _round_up(n, 8))
    npad = _round_up(n, block_n)

    xp = jnp.zeros((npad, dp), x.dtype).at[:n, :d].set(x)
    wp = jnp.zeros((1, dp), w.dtype).at[0, :d].set(w)
    yp = jnp.zeros((1, npad), y.dtype).at[0, :n].set(y)

    out = hinge_block_grad_padded(wp, xp, yp, c_over_n=c / n, block_n=block_n,
                                  interpret=interpret)
    return out[0, :d]
