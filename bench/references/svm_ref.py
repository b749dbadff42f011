"""DMS-SVM (the paper's Algorithm 3) written as what it computes.

K workers each take the next ``block_size`` rows of their own shard, all
from the same incoming ``w``; the synchronized ``w`` is the mean of their
updated weights, which is one subgradient step over the K·block rows
together:

    w ← w − α·(w − C·mean_i(viol_i·y_i·x_i)),  viol_i = 1{1 − y_i⟨w,x_i⟩ > 0}

with ``α = 1/(1+t)`` in epoch t. Every product is taken elementwise in
float32 and summed in float32 (``"highest"``): no matrix unit, whose
passes over bfloat16 pieces differ from one backend to another.
``"high"`` is the control: every product taken as three bfloat16 pieces
(hi·hi + hi·lo + lo·hi), as a TPU computes ``Precision.HIGH``, written
out so that it reads the same on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    # reduce_precision, not a round trip through bfloat16, which a
    # compiler may drop as excess precision
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def products(a, b, precision: str):
    """Elementwise a·b (broadcast) in float32, or in three bfloat16
    pieces."""
    if precision == "highest":
        return a * b
    if precision == "high":
        (ah, al), (bh, bl) = _split(a), _split(b)
        return ah * bh + (ah * bl + al * bh)
    raise ValueError(precision)


@functools.partial(jax.jit, static_argnames=("epochs", "block_size", "c",
                                             "precision"))
def dms(xs, ys, *, epochs: int, block_size: int, c: float = 1.0,
        precision: str = "highest"):
    """xs (K, n_local, d), ys (K, n_local) → the synchronized w from
    w = 0 after ``epochs`` passes."""
    k, n_local, d = xs.shape
    nb = n_local // block_size

    def block(w, i, alpha):
        x = jax.lax.dynamic_slice(xs, (0, i * block_size, 0),
                                  (k, block_size, d)).reshape(-1, d)
        y = jax.lax.dynamic_slice(ys, (0, i * block_size),
                                  (k, block_size)).reshape(-1)
        margin = jnp.sum(products(x, w[None, :], precision), axis=1)
        viol = (1.0 - y * margin > 0).astype(w.dtype)
        pull = jnp.sum(products((viol * y)[:, None], x, precision), axis=0)
        grad = w - c * pull / x.shape[0]
        return w - alpha * grad

    def epoch(w, t):
        alpha = 1.0 / (1.0 + t.astype(jnp.float32))
        w, _ = jax.lax.scan(lambda w, i: (block(w, i, alpha), None), w,
                            jnp.arange(nb))
        return w, None

    w, _ = jax.lax.scan(epoch, jnp.zeros((d,), jnp.float32),
                        jnp.arange(epochs))
    return w


@jax.jit
def accuracy(w, x, y):
    pred = jnp.where(jnp.matmul(x, w, precision=_HIGHEST) >= 0, 1.0, -1.0)
    return jnp.mean((pred == y).astype(jnp.float32))
