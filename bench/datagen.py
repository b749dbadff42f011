"""Inputs and weights made from ``--seed``, by the benchmark itself.

One generator per kind of input, driven by the numbers in a cell's and a
configuration's files:

* ``svm_rows``: the stand-in for a paper dataset (a unit-norm separating
  hyperplane, Bernoulli sparsity mask, label flips), drawn on the device
  with ``jax.random``. It follows ``repro.data.synthetic.make_svm_dataset``
  in law, not in values: that one draws with numpy on the host.
* ``lm_batch``: the zipf token stream of ``repro.data.synthetic
  .synthetic_lm_batch``, copied, so that the feed is the benchmark's.
* ``lm_params_fn``: seeded weights of a llama-style decoder in the float32
  layout the trainer keeps, made on the device in one jitted call.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def key(seed: int, stream: int) -> jax.Array:
    """A key per (seed, stream). Seeds above 32 bits keep their high bits."""
    k = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(k, stream)


# ---------------------------------------------------------------- SVM data

def svm_hyperplane(k: jax.Array, d: int) -> jax.Array:
    w = jax.random.normal(k, (d,), jnp.float32)
    return w / jnp.linalg.norm(w)


def svm_rows(k: jax.Array, w_true: jax.Array, n: int, density: float,
             label_noise: float):
    """``n`` rows: x (n, d) float32 with about ``density`` of its entries
    nonzero (at least one per row), y (n,) in {-1, +1} by the side of the
    hyperplane, a ``label_noise`` share flipped."""
    d = w_true.shape[0]
    kx, km, ke, kf = jax.random.split(k, 4)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    mask = jax.random.uniform(km, (n, d)) < density
    fill = jax.random.randint(ke, (n,), 0, d)
    empty = ~jnp.any(mask, axis=1)
    mask = mask | (empty[:, None]
                   & (jnp.arange(d)[None, :] == fill[:, None]))
    x = jnp.where(mask, x, 0.0)
    y = jnp.where(jnp.matmul(x, w_true, precision=_HIGHEST) >= 0, 1.0, -1.0)
    flip = jax.random.uniform(kf, (n,)) < label_noise
    return x, jnp.where(flip, -y, y).astype(jnp.float32)


def svm_train(seed: int, workers: int, n_local: int, d: int, density: float,
              label_noise: float, sharding):
    """Each worker's training rows, made in place on its own device:
    xs (K, n_local, d), ys (K, n_local) with ``sharding`` over K."""
    def make(seed_key):
        w_true = svm_hyperplane(jax.random.fold_in(seed_key, 0), d)
        keys = jax.random.split(jax.random.fold_in(seed_key, 1), workers)
        return jax.vmap(lambda kk: svm_rows(kk, w_true, n_local, density,
                                            label_noise))(keys)
    return jax.jit(make, out_shardings=(sharding, sharding))(key(seed, 0))


def svm_test(seed: int, n: int, d: int, density: float, label_noise: float):
    """Held-out rows from the same hyperplane as ``svm_train``."""
    def make(seed_key):
        w_true = svm_hyperplane(jax.random.fold_in(seed_key, 0), d)
        return svm_rows(jax.random.fold_in(seed_key, 2), w_true, n, density,
                        label_noise)
    return jax.jit(make)(key(seed, 0))


# ----------------------------------------------------------------- LM data

def lm_batch(seed: int, step: int, batch: int, seq_len: int,
             vocab: int) -> Dict[str, np.ndarray]:
    """Deterministic (seed, step) → next-token batch, zipf(1.2) ids folded
    into the vocabulary; targets are the tokens shifted by one."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    raw = rng.zipf(1.2, size=(batch, seq_len + 1)).astype(np.int64)
    tokens = (raw % vocab).astype(np.int32)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def lm_shapes(c: dict) -> dict:
    """The weight layout: embedding (V, D); per layer, stacked on a
    leading L axis, the two RMSNorm scales, wq (D, H, hd), wk/wv (D, KV,
    hd), wo (H, hd, D) and the SwiGLU w_gate/w_up (D, F), w_down (F, D);
    the final norm."""
    n, d, f = c["num_hidden_layers"], c["hidden_size"], \
        c["intermediate_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    return {
        "embed": {"embedding": (c["vocab_size"], d)},
        "layers": {
            "ln1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                     "wv": (n, d, kv, hd), "wo": (n, h, hd, d)},
            "ln2": {"scale": (n, d)},
            "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f),
                    "w_down": (n, f, d)},
        },
        "final_norm": {"scale": (d,)},
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, int) for e in x)


def _init(path: str, shape, k):
    if path.endswith("scale"):
        return jnp.ones(shape, jnp.float32)
    if path == "embed/embedding":
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    # a projection: normal over its fan-in (all axes but the output ones)
    fan_in = shape[1] * (shape[2] if path.endswith("wo") else 1)
    return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)


def lm_params_fn(c: dict):
    """``fn(key) -> params``: float32 weights from a key, jittable."""
    shapes = lm_shapes(c)
    flat, tree = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    names = ["/".join(p.key for p in path) for path, _ in flat]

    def fn(k):
        keys = jax.random.split(k, len(flat))
        return jax.tree.unflatten(
            tree, [_init(n, s, kk)
                   for n, (_, s), kk in zip(names, flat, keys)])
    return fn
