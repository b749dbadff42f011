"""Fused hinge block-subgradient Pallas kernel.

Computes, in one pass over the block,

    grad = w − (C/n)·Σᵢ 1{1 − yᵢ⟨xᵢ,w⟩ > 0}·yᵢ·xᵢ

which is the SVM inner loop (margins matvec + masked accumulation matvec)
fused so X is read from HBM exactly once. The grid walks row-blocks of X
sequentially (TPU grid order), accumulating the masked sum into the output
ref in VMEM; the final grid step folds in ``w`` and the ``C/n`` scale.

Tiling: X block = (block_n, d). d is padded to a lane multiple (128) by
``ops.py``; block_n is sublane-aligned (multiple of 8). For the paper's
largest dataset (Epsilon, d=2000→2048) a 512-row block is
512·2048·4B = 4 MiB of VMEM — inside the ~16 MiB v5e budget with headroom
for w, y and the accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hinge_kernel(c_over_n, w_ref, x_ref, y_ref, o_ref):
    i = pl.program_id(0)
    n_blocks = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...]                       # (1, d)
    x = x_ref[...]                       # (bn, d)
    y = y_ref[...]                       # (1, bn)
    # f32 passes: a margin's sign picks the subgradient branch
    hi = jax.lax.Precision.HIGHEST
    margins = 1.0 - y * jax.lax.dot_general(
        w, x, (((1,), (1,)), ((), ())), precision=hi)  # (1, bn) = w·xᵀ
    viol = jnp.where(margins > 0, y, 0.0)          # yᵢ where violated else 0
    # (1, bn) @ (bn, d) → (1, d) masked accumulation
    o_ref[...] += jax.lax.dot_general(viol, x, (((1,), (0,)), ((), ())),
                                      precision=hi)

    @pl.when(i == n_blocks - 1)
    def _finish():
        o_ref[...] = w - c_over_n * o_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "c_over_n", "interpret"))
def hinge_block_grad_padded(w2: jax.Array, x: jax.Array, y2: jax.Array, *,
                            c_over_n: float, block_n: int,
                            interpret: bool = False) -> jax.Array:
    """w2: (1, d) · x: (n, d) · y2: (1, n), all padded/aligned. → (1, d)."""
    n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_hinge_kernel, c_over_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),          # w: resident
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),    # X row-block
            pl.BlockSpec((1, block_n), lambda i: (0, i)),    # y row-block
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),    # accumulator
        out_shape=jax.ShapeDtypeStruct((1, d), x.dtype),
        interpret=interpret,
    )(w2, x, y2)
