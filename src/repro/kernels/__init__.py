"""Pallas TPU kernels for the framework's compute hot spots.

Each subpackage is ``kernel.py`` (``pl.pallas_call`` + explicit BlockSpec
VMEM tiling, TPU target), ``ops.py`` (jit'd public wrapper with padding /
layout glue and an ``interpret=`` switch), and ``ref.py`` (pure-jnp oracle
the tests sweep against). Every wrapper resolves ``interpret=None`` through
:func:`default_interpret`, one rule for all kernels: compiled on an
accelerator, interpreted only where Pallas has no compiled lowering.

The paper itself has no kernel-level contribution (its optimization is the
sync schedule); these kernels cover the substrate's hot spots:

* ``hinge``            — fused SVM block-subgradient (the paper's inner loop)
* ``flash_attention``  — tiled online-softmax attention (train/prefill)
* ``ssd``              — Mamba2 state-space-duality chunk scan
* ``quant``            — int8 pack/unpack for compressed MSF sync
"""
from __future__ import annotations

import functools

import jax


def default_interpret() -> bool:
    """Interpret only where Pallas cannot compile (CPU backends)."""
    return jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm")


def auto_interpret(op):
    """Resolve an op's ``interpret=None`` by :func:`default_interpret` at
    call time, before the op's jit cache is consulted."""
    @functools.wraps(op)
    def call(*args, interpret=None, **kwargs):
        if interpret is None:
            interpret = default_interpret()
        return op(*args, interpret=interpret, **kwargs)
    return call
