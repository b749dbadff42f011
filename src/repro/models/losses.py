"""Cross-entropy with optional sequence chunking.

Full logits at LM scale are the single biggest activation: (B, S, V) fp32
for qwen3 at train_4k is ~600 GB global. ``chunked_ce`` scans the sequence
in ``chunk``-sized slices, computing logits + log-softmax per slice inside a
``jax.checkpoint`` (so the backward pass recomputes each slice instead of
keeping all of them live). Peak logits memory drops S/chunk ×; FLOPs for
the recompute add one extra logits matmul — the classic memory/compute
trade, accounted for in the roofline's MODEL_FLOPS/HLO_FLOPS ratio.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.telemetry import scope
from repro.sharding import constrain



from repro import flags as _flags


def _scan(*args, **kw):
    kw.setdefault("unroll", _flags.scan_unroll_arg())
    return jax.lax.scan(*args, **kw)

def _ce_block(x: jax.Array, table: jax.Array, targets: jax.Array,
              valid: jax.Array, logits_scaling: float = 1.0
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, C, D) · table: (V, D) · targets: (B, C) → (sum_nll, n_valid).
    The float32 logits are divided by ``logits_scaling`` (left alone at
    1)."""
    logits = jnp.einsum("bcd,vd->bcv", x, table.astype(x.dtype))
    logits = constrain(logits, "batch", "seq", "vocab")
    logits = logits.astype(jnp.float32)
    if logits_scaling != 1.0:
        logits = logits / logits_scaling
    lse = jax.nn.logsumexp(logits, axis=-1)
    # gather-free target pick (iota-select fuses; take_along_axis is a
    # gather, which the SPMD partitioner mishandles in manual subgroups)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    tgt = jnp.sum(jnp.where(iota == targets[..., None], logits, 0.0),
                  axis=-1)
    nll = (lse - tgt) * valid
    return jnp.sum(nll), jnp.sum(valid)


def ce_loss(x: jax.Array, table: jax.Array, targets: jax.Array,
            mask: Optional[jax.Array] = None, chunk: int = 0,
            logits_scaling: float = 1.0) -> jax.Array:
    """Mean next-token NLL. x: (B, S, D) final hidden · table: (V, D).

    ``mask`` (B, S) ∈ {0,1} selects positions contributing to the loss
    (e.g. text-only positions for the VLM). ``chunk`` > 0 scans the seq dim
    in slices of that size (must divide S). The logits are divided by
    ``logits_scaling`` before the softmax, in each slice.
    """
    with scope("lm.loss"):
        b, s, d = x.shape
        valid = (jnp.ones((b, s), jnp.float32) if mask is None
                 else mask.astype(jnp.float32))

        if chunk <= 0 or s <= chunk or s % chunk != 0:
            total, count = _ce_block(x, table, targets, valid,
                                     logits_scaling)
            return total / jnp.maximum(count, 1.0)

        nchunk = s // chunk
        xs = x.reshape(b, nchunk, chunk, d).swapaxes(0, 1)  # (n, B, C, D)
        ts = targets.reshape(b, nchunk, chunk).swapaxes(0, 1)
        vs = valid.reshape(b, nchunk, chunk).swapaxes(0, 1)

        block = jax.checkpoint(
            lambda xc, tc, vc: _ce_block(xc, table, tc, vc, logits_scaling))

        def body(carry, inp):
            tot, cnt = carry
            xc, tc, vc = inp
            t, c = block(xc, tc, vc)
            return (tot + t, cnt + c), None

        (total, count), _ = _scan(body, (jnp.float32(0), jnp.float32(0)),
                                  (xs, ts, vs))
        return total / jnp.maximum(count, 1.0)
