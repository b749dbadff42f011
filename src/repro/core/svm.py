"""Paper-faithful SGD-SVM: Algorithms 1 (SGD), 2 (SRDMS), 3 (DMS).

Math (paper §III): hinge objective ``J = ½‖w‖² + C·Σ max(0, 1 − y⟨w,x⟩)``,
per-sample subgradient ``∇J = w`` when the margin is met, ``w − C·y·x``
otherwise, update ``w ← w − α∇J`` with ``α = 1/(1+t)`` decaying per epoch.

Block semantics (§IV-B): within a block every point computes its update from
the *same* incoming ``w`` and the block's outgoing weight is the average of
the per-point updated weights — algebraically

    w' = mean_i(w − α∇Jᵢ(w)) = w − α·mean_i(∇Jᵢ(w)),

i.e. the paper's model-synchronizing SGD is mini-batch subgradient descent
with an effective batch of ``K·s_b``. That identity is the paper's own
validation device (DMS ≡ its sequential replica) and is asserted in tests:

    DMS(K workers, block s_b)  ≡  SRDMS(block K·s_b)   (exactly, in fp64)

Three execution backends share the block math:

* :func:`seq_sgd`      — Algorithm 1, ``lax.scan`` over points.
* :func:`srdms`        — Algorithm 2, ``lax.scan`` over blocks.
* :func:`dms`          — Algorithm 3; ``backend="vmap"`` simulates K workers
  on one device (bit-identical math), ``backend="shard_map"`` runs manual
  collectives over the mesh data axis (``MPI_AllReduce`` → ``lax.pmean``).

``grad_impl="pallas"`` routes the block-gradient hot spot through the fused
Pallas kernel (:mod:`repro.kernels.hinge`).

``overlap`` lifts the sync engine's overlap modes (see
:mod:`repro.core.sync`) onto the paper-faithful path:

* ``"none"``    — blocking ``MPI_AllReduce`` at every block boundary (the
  paper; keeps the DMS ≡ SRDMS identity bit-exact).
* ``"delayed"`` — stale-by-one averaging: block *i*'s mean delta is applied
  at the end of block *i+1*, so the collective overlaps the next block's
  compute. Workers carry ``pending = meanΔ − ownΔ`` and stay within one
  block's drift of the anchor.
* ``"chunked"`` — ``w`` is split into ``chunks`` contiguous segments
  (zero-padded to equal length) and one segment is value-averaged per
  block, shrinking per-sync wire bytes ``chunks``× (each coordinate syncs
  every ``chunks`` blocks).

``topology`` lifts the sync engine's gossip axis onto the same path:

* ``"all"``      — the paper's global ``MPI_AllReduce`` (``lax.pmean``).
* ``"ring"``     — each worker averages with its two ``lax.ppermute``
  neighbors (``w ← (w + w_left + w_right)/3``): O(1) neighbor bytes per
  sync independent of K, and no global barrier for a straggler to stall.
* ``"pairwise"`` — rotating disjoint odd–even pairs average with weight ½
  (round parity alternates the pairing); requires an even worker count.

Precision: a margin's sign picks the subgradient branch, so every margin
and the block-gradient sum are computed at ``Precision.HIGHEST``. A TPU's
default f32 matmul rounds its operands to bf16; that flips margins near
zero, and one flipped point sends the whole SGD trajectory elsewhere
(DMS would no longer track SRDMS). On the CPU the setting changes nothing.

Gossip workers only reach consensus geometrically (factor λ₂ per round —
:func:`repro.core.costmodel.gossip_lambda2`); the mixing matrix is doubly
stochastic, so the worker mean is invariant and the final flush
(``mean_K(w)``) returns the exact consensus target. The ``vmap`` backend
simulates gossip with the same static mixing matrices the cost model
analyzes; the ``shard_map`` backend emits real ``ppermute``s.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.telemetry import scope

_HIGHEST = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def hinge_objective(w: jax.Array, x: jax.Array, y: jax.Array,
                    c: float = 1.0) -> jax.Array:
    """Paper eq. (2): ½‖w‖² + C·Σ hinge."""
    margins = 1.0 - y * jnp.matmul(x, w, precision=_HIGHEST)
    return 0.5 * jnp.dot(w, w) + c * jnp.sum(jnp.maximum(0.0, margins))


def accuracy(w: jax.Array, x: jax.Array, y: jax.Array) -> jax.Array:
    pred = jnp.where(jnp.matmul(x, w, precision=_HIGHEST) >= 0, 1.0, -1.0)
    return jnp.mean(pred == y)


def _padded_width(d: int, chunks: int) -> int:
    """Feature count padded up to a chunk multiple — the single source of
    the chunked carry width (``_dms_vmap`` / ``_carry_init`` /
    ``dms_stepper_init`` must agree or carries go shape-incompatible)."""
    return -(-d // chunks) * chunks


def block_grad(w: jax.Array, xb: jax.Array, yb: jax.Array, c: float,
               impl: str = "jnp") -> jax.Array:
    """Mean subgradient of a block (same incoming w for every point).

    ``∇ = w − C·mean_i(violᵢ·yᵢ·xᵢ)`` where viol = 1{1 − y⟨w,x⟩ > 0}.
    """
    if impl == "pallas":
        from repro.kernels.hinge import ops as hinge_ops
        return hinge_ops.hinge_block_grad(w, xb, yb, c)
    margins = 1.0 - yb * jnp.matmul(xb, w, precision=_HIGHEST)
    viol = (margins > 0).astype(w.dtype)
    return w - c * jnp.matmul(viol * yb, xb, precision=_HIGHEST) / xb.shape[0]


def _point_update(w, x, y, alpha, c):
    """Algorithm 1 inner step (single point)."""
    margin = 1.0 - y * jnp.dot(x, w, precision=_HIGHEST)
    grad = jnp.where(margin > 0, w - c * y * x, w)
    return w - alpha * grad


# ---------------------------------------------------------------------------
# Algorithm 1 — sequential SGD
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("epochs", "c"))
def seq_sgd(w0: jax.Array, x: jax.Array, y: jax.Array, *, epochs: int,
            c: float = 1.0) -> jax.Array:
    def epoch(w, t):
        alpha = 1.0 / (1.0 + t.astype(w.dtype))
        def point(w, xy):
            xi, yi = xy
            return _point_update(w, xi, yi, alpha, c), None
        w, _ = jax.lax.scan(point, w, (x, y))
        return w, None
    w, _ = jax.lax.scan(epoch, w0, jnp.arange(epochs))
    return w


# ---------------------------------------------------------------------------
# Algorithm 2 — SRDMS (sequential replica of the distributed algorithm)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("epochs", "block_size", "c", "grad_impl",
                                    "with_history", "eval_every_sync"))
def srdms(w0: jax.Array, x: jax.Array, y: jax.Array, *, epochs: int,
          block_size: int, c: float = 1.0, grad_impl: str = "jnp",
          x_cv: Optional[jax.Array] = None, y_cv: Optional[jax.Array] = None,
          with_history: bool = False, eval_every_sync: bool = False):
    """Algorithm 2. Data is truncated to a whole number of blocks.

    With ``with_history`` (and cv arrays), returns per-epoch
    (objective, cv_accuracy). ``eval_every_sync=True`` reproduces the
    paper's §V-C2 methodology exactly: the cross-validation accuracy and
    objective are recomputed at EVERY model synchronization (block) — the
    per-sync overhead whose dilution with larger blocks is the paper's
    Figs 2/4 sequential-time effect.
    """
    n, d = x.shape
    nb = n // block_size
    xb = x[: nb * block_size].reshape(nb, block_size, d)
    yb = y[: nb * block_size].reshape(nb, block_size)

    def epoch(w, t):
        alpha = 1.0 / (1.0 + t.astype(w.dtype))
        def block(w, xy):
            xblk, yblk = xy
            w = w - alpha * block_grad(w, xblk, yblk, c, grad_impl)
            if eval_every_sync:
                obj = hinge_objective(w, x, y, c)
                acc = accuracy(w, x_cv, y_cv) if x_cv is not None else jnp.nan
                return w, (obj, acc)
            return w, None
        w, sync_hist = jax.lax.scan(block, w, (xb, yb))
        if with_history:
            obj = hinge_objective(w, x, y, c)
            acc = accuracy(w, x_cv, y_cv) if x_cv is not None else jnp.nan
            return w, (obj, acc)
        if eval_every_sync:
            # keep only the epoch-final sync stats (static shapes)
            return w, (sync_hist[0][-1], sync_hist[1][-1])
        return w, None

    w, hist = jax.lax.scan(epoch, w0, jnp.arange(epochs))
    return (w, hist) if (with_history or eval_every_sync) else w


# ---------------------------------------------------------------------------
# Algorithm 3 — DMS (distributed model synchronizing SGD)
# ---------------------------------------------------------------------------

def _shard_data(x: np.ndarray, y: np.ndarray, k: int):
    """Equal-load split across K workers (paper's load balancing)."""
    n = (x.shape[0] // k) * k
    return (x[:n].reshape(k, n // k, -1), y[:n].reshape(k, n // k))


@functools.partial(jax.jit,
                   static_argnames=("epochs", "block_size", "c", "grad_impl",
                                    "overlap", "chunks", "topology",
                                    "gossip_async"))
def _dms_vmap(w0, xs, ys, *, epochs: int, block_size: int, c: float,
              grad_impl: str, overlap: str = "none", chunks: int = 4,
              topology: str = "all", gossip_async: bool = False):
    """K simulated workers: xs (K, n_local, d). Every worker holds its own
    w between syncs; sync = mean over the worker dim after each block
    (blocking), stale-by-one (delayed) or one w-segment per block (chunked).
    ``topology != "all"`` replaces the worker mean with the static gossip
    mixing matrix (``w ← M w``, M from costmodel.mixing_matrices — the same
    matrices whose λ₂ the auto-tuner's guardrail reads). ``gossip_async``
    mixes the *last transmitted* snapshot instead of the current one: the
    boundary applies the carried stale correction, then banks
    ``M·(post-correction w) − w`` for the next boundary."""
    k, n_local, d = xs.shape
    nb = n_local // block_size
    xb = xs[:, : nb * block_size].reshape(k, nb, block_size, d)
    yb = ys[:, : nb * block_size].reshape(k, nb, block_size)
    # scan over blocks outside, vmap over workers inside
    xb = jnp.swapaxes(xb, 0, 1)   # (nb, K, bs, d)
    yb = jnp.swapaxes(yb, 0, 1)

    if topology != "all":
        from repro.core import costmodel
        mats = [jnp.asarray(m, w0.dtype)
                for m in costmodel.mixing_matrices(k, topology)]

        def mix(w, rnd):
            """w (K, cols) ← M_rnd w; rnd selects the pairwise parity."""
            mm = functools.partial(jnp.matmul, precision=_HIGHEST)
            if len(mats) == 1:
                return mm(mats[0], w)
            return jax.lax.cond(rnd % 2 == 0, lambda v: mm(mats[0], v),
                                lambda v: mm(mats[1], v), w)

        dp = _padded_width(d, chunks) if overlap == "chunked" else d
        seg = dp // chunks
        delayed = overlap == "delayed"

        def epoch(carry, t):
            alpha = 1.0 / (1.0 + t.astype(w0.dtype))

            def block(carry, xy):
                # carry: (wk, pending, cnt) under delayed/async, (wk, cnt)
                # else — the (K, dp) pending buffer only exists where read
                wk, cnt = (carry[0], carry[-1])
                xblk, yblk = xy
                grads = jax.vmap(
                    lambda ww, xw, yw: block_grad(ww[:d], xw, yw, c,
                                                  grad_impl)
                )(wk, xblk, yblk)
                w_end = wk - alpha * (grads if dp == d else
                                      jnp.pad(grads, ((0, 0), (0, dp - d))))
                if gossip_async:
                    # apply the stale correction banked at the previous
                    # boundary, then bank M·(post-correction snapshot) − it
                    # for the next one — the double-buffered exchange as a
                    # matrix recurrence (zero drift ⇒ w_t = M w_{t−1})
                    new_w = w_end + carry[1]
                    g = mix(new_w, cnt) - new_w
                    return (new_w, g, cnt + 1), None
                if overlap == "none":
                    return (mix(w_end, cnt), cnt + 1), None
                if delayed:
                    # apply the previous boundary's gossip correction; this
                    # boundary's mix feeds only the carried pending state
                    g = mix(w_end, cnt) - w_end
                    return (w_end + carry[1], g, cnt + 1), None
                rows = jax.lax.dynamic_slice(
                    w_end, (0, (cnt % chunks) * seg), (k, seg))
                mrow = mix(rows, cnt // chunks)
                w_new = jax.lax.dynamic_update_slice(
                    w_end, mrow, (0, (cnt % chunks) * seg))
                return (w_new, cnt + 1), None

            carry, _ = jax.lax.scan(block, carry, (xb, yb))
            return carry, None

        wk0 = jnp.zeros((k, dp), w0.dtype).at[:, :d].set(
            jnp.broadcast_to(w0, (k, d)))
        cnt0 = jnp.zeros((), jnp.int32)
        carry0 = ((wk0, jnp.zeros((k, dp), w0.dtype), cnt0)
                  if (delayed or gossip_async) else (wk0, cnt0))
        carry, _ = jax.lax.scan(epoch, carry0, jnp.arange(epochs))
        # flush: the worker mean is invariant under doubly stochastic
        # mixing — the exact consensus target
        return jnp.mean(carry[0], axis=0)[:d]

    if overlap == "none":
        def epoch(w, t):
            alpha = 1.0 / (1.0 + t.astype(w.dtype))
            def block(w, xy):
                xblk, yblk = xy        # (K, bs, d), (K, bs)
                grads = jax.vmap(
                    lambda xw, yw: block_grad(w, xw, yw, c, grad_impl)
                )(xblk, yblk)
                w_locals = w - alpha * grads      # (K, d) per-worker models
                return jnp.mean(w_locals, axis=0), None  # MPI_AllReduce / K
            w, _ = jax.lax.scan(block, w, (xb, yb))
            return w, None

        w, _ = jax.lax.scan(epoch, w0, jnp.arange(epochs))
        return w

    if overlap == "delayed":
        # carry: per-worker models + pending correction (meanΔ − ownΔ of the
        # previous block). This block's output never consumes this block's
        # mean — the collective has the whole next block to land.
        def epoch(carry, t):
            wk, pending = carry
            alpha = 1.0 / (1.0 + t.astype(wk.dtype))
            def block(carry, xy):
                wk, pending = carry
                xblk, yblk = xy
                grads = jax.vmap(
                    lambda ww, xw, yw: block_grad(ww, xw, yw, c, grad_impl)
                )(wk, xblk, yblk)
                delta = -alpha * grads            # (K, d) local block deltas
                mean = jnp.mean(delta, axis=0)    # the (overlappable) sync
                return (wk + delta + pending, mean[None] - delta), None
            carry, _ = jax.lax.scan(block, (wk, pending), (xb, yb))
            return carry, None

        carry0 = (jnp.broadcast_to(w0, (k, d)), jnp.zeros((k, d), w0.dtype))
        (wk, _), _ = jax.lax.scan(epoch, carry0, jnp.arange(epochs))
        # flush: workers sit at anchor + ownΔ_last; their mean is the fully
        # synchronized model anchor + meanΔ_last
        return jnp.mean(wk, axis=0)

    if overlap == "chunked":
        dp = _padded_width(d, chunks)
        seg = dp // chunks
        def epoch(carry, t):
            alpha = 1.0 / (1.0 + t.astype(w0.dtype))
            def block(carry, xy):
                wk, cnt = carry                   # (K, dp), i32
                xblk, yblk = xy
                grads = jax.vmap(
                    lambda ww, xw, yw: block_grad(ww[:d], xw, yw, c, grad_impl)
                )(wk, xblk, yblk)
                w_end = wk - alpha * jnp.pad(grads, ((0, 0), (0, dp - d)))
                idx = cnt % chunks
                rows = jax.lax.dynamic_slice(w_end, (0, idx * seg), (k, seg))
                mrow = jnp.broadcast_to(jnp.mean(rows, axis=0), (k, seg))
                w_new = jax.lax.dynamic_update_slice(w_end, mrow,
                                                     (0, idx * seg))
                return (w_new, cnt + 1), None
            carry, _ = jax.lax.scan(block, carry, (xb, yb))
            return carry, None

        wk0 = jnp.zeros((k, dp), w0.dtype).at[:, :d].set(
            jnp.broadcast_to(w0, (k, d)))
        carry0 = (wk0, jnp.zeros((), jnp.int32))
        (wk, _), _ = jax.lax.scan(epoch, carry0, jnp.arange(epochs))
        return jnp.mean(wk, axis=0)[:d]

    raise ValueError(f"unknown overlap mode: {overlap!r}")


def _make_worker_block(axis: str, *, c: float, grad_impl: str, overlap: str,
                       chunks: int, d: int, topology: str = "all",
                       gossip_async: bool = False):
    """One worker's block (compute + boundary sync), inside shard_map with
    ``axis`` manual. ``carry`` is a dict per overlap mode:

        none:    {"w": (d,)}                    — replicated after each sync
        delayed: {"w": (d,), "pending": (d,)}   — pending = meanΔ − ownΔ
        chunked: {"w": (dp,), "cnt": i32}       — dp = d padded to chunks·seg

    ``topology != "all"`` swaps every ``pmean`` for a ``ppermute`` neighbor
    mix (:func:`repro.core.sync.gossip_mix`); ``"pairwise"`` adds a ``cnt``
    round counter to the none/delayed carries for the pairing parity, and
    the delayed pending becomes ``mix(w_end) − w_end`` (value-form gossip —
    workers never share an anchor, so a delta-only exchange would let the
    anchors drift apart unboundedly).

    Under ``delayed`` the returned ``w`` depends only on the *previous*
    boundary's correction; this boundary's collective output feeds only
    ``pending``, so it is not on this or the next block's compute critical
    path.

    ``gossip_async`` (gossip only, ``overlap="none"``) double-buffers the
    exchange: carry gains ``sent``/``mixbuf`` (the snapshot transmitted at
    the previous boundary and the neighbor payloads received there); the
    boundary applies the stale correction ``mixbuf + M_ii·sent − sent``
    first, then ppermutes the post-correction model into the buffers for
    the *next* boundary — a worker never consumes a neighbor's
    current-round value.
    """
    from repro.core import sync as _sync
    gossip = topology != "all"
    if gossip_async:
        assert gossip and overlap == "none", (topology, overlap)

    def exchange(v, cnt):
        """Boundary exchange: global mean, or topology neighbor mix."""
        with scope("svm.sync"):
            if gossip:
                return _sync.gossip_mix(v, axis, topology, round_idx=cnt)
            return jax.lax.pmean(v, axis)

    def bump(out, carry):
        if gossip and topology == "pairwise" and overlap != "chunked":
            out["cnt"] = carry["cnt"] + 1
        return out

    def block(carry, xblk, yblk, alpha):
        cnt = carry.get("cnt")
        if gossip_async:
            w = carry["w"]
            w_self = _sync.gossip_self_weight(topology)
            with scope("svm.block"):
                w_end = w - alpha * block_grad(w, xblk, yblk, c, grad_impl)
            with scope("svm.sync"):
                new_w = (w_end + carry["mixbuf"]
                         + (w_self - 1.0) * carry["sent"])
                recv = _sync.gossip_recv(new_w, axis, topology,
                                         round_idx=cnt)
            return bump({"w": new_w, "sent": new_w, "mixbuf": recv}, carry)
        if overlap == "none":
            w = carry["w"]
            with scope("svm.block"):
                w_local = w - alpha * block_grad(w, xblk, yblk, c, grad_impl)
            return bump({"w": exchange(w_local, cnt)}, carry)
        if overlap == "delayed":
            w = carry["w"]
            with scope("svm.block"):
                delta = -alpha * block_grad(w, xblk, yblk, c, grad_impl)
                w_end = w + delta
            if gossip:
                pending = exchange(w_end, cnt) - w_end   # overlappable
            else:
                with scope("svm.sync"):
                    pending = jax.lax.pmean(delta, axis) - delta
            with scope("svm.block"):
                w_new = w_end + carry["pending"]
            return bump({"w": w_new, "pending": pending}, carry)
        # chunked: one w-segment value-exchanged per block
        w = carry["w"]                               # (dp,)
        dp = w.shape[0]
        seg = dp // chunks
        with scope("svm.block"):
            g = block_grad(w[:d], xblk, yblk, c, grad_impl)
            w_end = w - alpha * jnp.pad(g, (0, dp - d))
        idx = carry["cnt"] % chunks
        with scope("svm.sync"):
            row = jax.lax.dynamic_slice(w_end, (idx * seg,), (seg,))
            row = exchange(row, carry["cnt"] // chunks)  # 1/chunks of bytes
            w_new = jax.lax.dynamic_update_slice(w_end, row, (idx * seg,))
        return {"w": w_new, "cnt": carry["cnt"] + 1}
    return block


def _needs_round(overlap: str, topology: str) -> bool:
    """Pairwise none/delayed carries a round counter for the pairing parity
    (chunked reuses its own cnt)."""
    return topology == "pairwise" and overlap != "chunked"


def _carry_init(w0, *, overlap: str, chunks: int, topology: str = "all",
                gossip_async: bool = False):
    """Initial per-worker carry (local, no leading worker dim)."""
    d = w0.shape[0]
    if gossip_async:
        sent, mixbuf = dms_async_buffers_init(w0, topology)
        carry = {"w": w0, "sent": sent, "mixbuf": mixbuf}
    elif overlap == "none":
        carry = {"w": w0}
    elif overlap == "delayed":
        carry = {"w": w0, "pending": jnp.zeros((d,), w0.dtype)}
    else:
        dp = _padded_width(d, chunks)
        carry = {"w": jnp.zeros((dp,), w0.dtype).at[:d].set(w0),
                 "cnt": jnp.zeros((), jnp.int32)}
    if _needs_round(overlap, topology):
        carry["cnt"] = jnp.zeros((), jnp.int32)
    return carry


def _carry_flush(carry, axis: str, *, overlap: str, d: int,
                 topology: str = "all"):
    """Collapse a worker's carry to the fully synchronized model."""
    if overlap == "none" and topology == "all":
        return carry["w"]
    with scope("svm.sync"):
        if overlap in ("none", "delayed"):
            # workers sit within one block's drift (delayed) or the gossip
            # consensus envelope; their mean is the synchronized model (the
            # mean is invariant under the doubly stochastic gossip mix)
            return jax.lax.pmean(carry["w"], axis)
        return jax.lax.pmean(carry["w"], axis)[:d]


def dms_job_counts(n_local: int, d: int, block_size: int, epochs: int,
                   overlap: str = "none", topology: str = "all",
                   chunks: int = 4, itemsize: int = 4) -> dict:
    """What one call of :func:`dms_shard_map_program` does on each worker.

    ``blocks`` local block updates and ``samples`` rows trained (the rows
    past the last whole block are left out); ``syncs`` exchanges under the
    ``svm.sync`` scope: one per block, plus the flush's mean wherever the
    carry is not already the synchronized model (any overlap or gossip);
    ``sync_bytes`` the values those exchanges take in (a chunked block's
    one segment, else the whole model), at ``itemsize`` bytes each. An
    exchange is one all-reduce under ``topology="all"``, one
    ``ppermute`` a neighbor otherwise."""
    blocks = epochs * (n_local // block_size)
    dp = _padded_width(d, chunks) if overlap == "chunked" else d
    per_block = dp // chunks if overlap == "chunked" else d
    flush = int(overlap != "none" or topology != "all")
    return {"blocks": blocks, "syncs": blocks + flush,
            "sync_bytes": itemsize * (blocks * per_block + flush * dp),
            "samples": blocks * block_size}


@functools.lru_cache(maxsize=64)
def dms_shard_map_program(mesh, axis: str = "data", *, epochs: int,
                          block_size: int, c: float = 1.0,
                          grad_impl: str = "jnp", overlap: str = "none",
                          chunks: int = 4, topology: str = "all",
                          gossip_async: bool = False):
    """The jitted program ``dms(backend="shard_map")`` runs:
    ``fn(w0, xs, ys) → w`` with ``xs (K, n_local, d)`` / ``ys (K, n_local)``
    sharded over ``axis`` (K = that axis' size). Real collectives: workers
    = mesh axis shards; sync = lax.pmean (``topology="all"``) or
    lax.ppermute neighbor mixing (gossip).

    Cached per configuration, so lowering it (``fn.lower(...).compile()``,
    to read the HLO or to compile for a described topology) and a
    ``dms`` call with the same arguments share one compilation."""

    def worker(w, x_local, y_local):
        d = w.shape[0]
        # x_local arrives as (1, n_local, d) — this worker's shard
        x_local, y_local = x_local[0], y_local[0]
        n_local, _ = x_local.shape
        nb = n_local // block_size
        xb = x_local[: nb * block_size].reshape(nb, block_size, d)
        yb = y_local[: nb * block_size].reshape(nb, block_size)
        blockfn = _make_worker_block(axis, c=c, grad_impl=grad_impl,
                                     overlap=overlap, chunks=chunks, d=d,
                                     topology=topology,
                                     gossip_async=gossip_async)

        def epoch(carry, t):
            alpha = 1.0 / (1.0 + t.astype(w.dtype))
            def blk(carry, xy):
                return blockfn(carry, xy[0], xy[1], alpha), None
            carry, _ = jax.lax.scan(blk, carry, (xb, yb))
            return carry, None

        carry, _ = jax.lax.scan(epoch, _carry_init(w, overlap=overlap,
                                                   chunks=chunks,
                                                   topology=topology,
                                                   gossip_async=gossip_async),
                                jnp.arange(epochs))
        return _carry_flush(carry, axis, overlap=overlap, d=d,
                            topology=topology)

    fn = jax.shard_map(worker, mesh=mesh,
                       in_specs=(P(), P(axis), P(axis)), out_specs=P(),
                       axis_names={axis}, check_vma=False)
    return jax.jit(fn)


def dms(w0: jax.Array, x: np.ndarray, y: np.ndarray, *, workers: int,
        epochs: int, block_size: int, c: float = 1.0,
        grad_impl: str = "jnp", backend: str = "vmap",
        mesh=None, axis: str = "data", overlap: str = "none",
        chunks: int = 4, topology: str = "all",
        gossip_async: bool = False) -> jax.Array:
    """Algorithm 3 entry point. ``block_size`` is points per worker per sync
    (the paper's MSF knob: larger block ⇒ lower sync frequency);
    ``overlap`` ∈ {"none", "delayed", "chunked"} selects how the residual
    sync is taken off the critical path and ``topology`` ∈ {"all", "ring",
    "pairwise"} which workers it couples (module docstring);
    ``gossip_async`` switches a gossip topology to the double-buffered
    unsynchronized-round exchange (requires ``overlap="none"``)."""
    if gossip_async and (topology == "all" or overlap != "none"):
        raise ValueError("gossip_async needs a gossip topology and "
                         f"overlap='none'; got topology={topology!r}, "
                         f"overlap={overlap!r}")
    xs, ys = _shard_data(np.asarray(x), np.asarray(y), workers)
    if backend == "vmap":
        return _dms_vmap(w0, jnp.asarray(xs), jnp.asarray(ys), epochs=epochs,
                         block_size=block_size, c=c, grad_impl=grad_impl,
                         overlap=overlap, chunks=chunks, topology=topology,
                         gossip_async=gossip_async)
    if backend == "shard_map":
        assert mesh is not None
        k = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
        if workers != k:
            raise ValueError(f"workers={workers} but mesh axis {axis!r} "
                             f"has {k} devices")
        # each worker's rows go straight to its own device
        xs, ys = jax.device_put((xs, ys), NamedSharding(mesh, P(axis)))
        fn = dms_shard_map_program(
            mesh, axis, epochs=epochs, block_size=block_size, c=c,
            grad_impl=grad_impl, overlap=overlap, chunks=chunks,
            topology=topology, gossip_async=gossip_async)
        return fn(w0, xs, ys)
    raise ValueError(backend)


# ---------------------------------------------------------------------------
# instrumented variant for the paper's timing-breakdown experiments
# ---------------------------------------------------------------------------

def dms_timed_steps(mesh, axis: str, *, block_size: int, c: float = 1.0,
                    grad_impl: str = "jnp", overlap: str = "none",
                    chunks: int = 4, topology: str = "all",
                    gossip_async: bool = False, telemetry=None):
    """Returns (compute_step, sync_step) jitted separately so benchmarks can
    time computation vs communication — the paper's Figs 10–12 methodology
    (they instrument around MPI_AllReduce the same way).

    ``telemetry`` (a :class:`repro.core.telemetry.BlockTelemetry`) wraps
    both returned steps with host-side timers: each compute call records
    ``block_size`` steps' compute time, each sync call one collective —
    the separated T_step/T_sync feed the MSF auto-tuner's adaptive
    controller and calibrate the simsync cluster simulator.

    ``overlap`` changes the sync step's signature (compute is unchanged —
    per-worker block update from per-worker models):

        none:    sync(w_locals) → w                       (blocking pmean)
        delayed: sync(w_start_locals, w_end_locals, pending)
                     → (w_new_locals, new_pending)        (stale-by-one)
        chunked: sync(w_end_locals, cnt) → w_new_locals   (one segment;
                 d must be divisible by ``chunks``; caller increments cnt)

    ``topology != "all"`` (supported for ``overlap="none"``) swaps the
    blocking pmean for the gossip neighbor mix; models stay per-worker:

        gossip:  sync(w_locals, cnt) → w_new_locals       (ppermute mix)
        async:   sync(w_locals, sent, mixbuf, cnt)
                     → (w_new_locals, new_sent, new_mixbuf)
                 (apply the stale correction, then the double-buffered
                  ppermute half-exchange; seed sent/mixbuf with
                  ``dms_async_buffers_init``)
    """
    gossip = topology != "all"
    if gossip and overlap != "none":
        raise ValueError("dms_timed_steps times gossip only for "
                         "overlap='none' (use dms_block_stepper otherwise)")
    if gossip_async and not gossip:
        raise ValueError("gossip_async needs topology='ring'/'pairwise'")

    def compute(w, xb, yb, alpha):
        # per-worker block update, NO sync. xb: (K, bs, d) sharded over axis.
        # w: replicated (d,) for blocking topology="all", per-worker (K, d)
        # otherwise (gossip never re-replicates the model).
        replicated_w = overlap == "none" and not gossip
        w_spec = P() if replicated_w else P(axis)
        def worker(w, xw, yw):
            wl = w if replicated_w else w[0]
            g = block_grad(wl, xw[0], yw[0], c, grad_impl)
            return (wl - alpha * g)[None]   # (1, d) → (K, d) globally
        f = jax.shard_map(worker, mesh=mesh,
                          in_specs=(w_spec, P(axis), P(axis)),
                          out_specs=P(axis),
                          axis_names={axis}, check_vma=False)
        return f(w, xb, yb)

    if gossip_async:
        from repro.core import sync as _sync
        w_self = _sync.gossip_self_weight(topology)

        def sync(w_locals, sent, mixbuf, cnt):
            def worker(wl, sl, bl, cnt):
                new_w = wl[0] + bl[0] + (w_self - 1.0) * sl[0]
                recv = _sync.gossip_recv(new_w, axis, topology,
                                         round_idx=cnt)
                return new_w[None], new_w[None], recv[None]
            f = jax.shard_map(worker, mesh=mesh,
                              in_specs=(P(axis), P(axis), P(axis), P()),
                              out_specs=(P(axis), P(axis), P(axis)),
                              axis_names={axis}, check_vma=False)
            return f(w_locals, sent, mixbuf, cnt)
    elif gossip:
        from repro.core import sync as _sync

        def sync(w_locals, cnt):
            def worker(wl, cnt):
                return _sync.gossip_mix(wl[0], axis, topology,
                                        round_idx=cnt)[None]
            f = jax.shard_map(worker, mesh=mesh, in_specs=(P(axis), P()),
                              out_specs=P(axis), axis_names={axis},
                              check_vma=False)
            return f(w_locals, cnt)
    elif overlap == "none":
        def sync(w_locals):
            def worker(wl):
                return jax.lax.pmean(wl[0], axis)
            f = jax.shard_map(worker, mesh=mesh, in_specs=(P(axis),),
                              out_specs=P(), axis_names={axis},
                              check_vma=False)
            return f(w_locals)
    elif overlap == "delayed":
        def sync(w_start_locals, w_end_locals, pending):
            def worker(ws, we, pend):
                delta = we[0] - ws[0]
                mean = jax.lax.pmean(delta, axis)
                return (we[0] + pend[0])[None], (mean - delta)[None]
            f = jax.shard_map(worker, mesh=mesh,
                              in_specs=(P(axis), P(axis), P(axis)),
                              out_specs=(P(axis), P(axis)),
                              axis_names={axis}, check_vma=False)
            return f(w_start_locals, w_end_locals, pending)
    elif overlap == "chunked":
        def sync(w_end_locals, cnt):
            d = w_end_locals.shape[-1]
            assert d % chunks == 0, (d, chunks)
            seg = d // chunks
            def worker(we, cnt):
                w = we[0]
                idx = cnt % chunks
                row = jax.lax.dynamic_slice(w, (idx * seg,), (seg,))
                row = jax.lax.pmean(row, axis)
                return jax.lax.dynamic_update_slice(w, row, (idx * seg,))[None]
            f = jax.shard_map(worker, mesh=mesh, in_specs=(P(axis), P()),
                              out_specs=P(axis), axis_names={axis},
                              check_vma=False)
            return f(w_end_locals, cnt)
    else:
        raise ValueError(f"unknown overlap mode: {overlap!r}")

    def scoped(name, fn):
        @functools.wraps(fn)
        def run(*args):
            with scope(name):
                return fn(*args)
        return run

    # the same scopes as the fused program's block and exchange
    compute_jit = jax.jit(scoped("svm.block", compute))
    sync_jit = jax.jit(scoped("svm.sync", sync))
    if telemetry is None:
        return compute_jit, sync_jit

    import time as _time

    def timed_compute(*args):
        t0 = _time.perf_counter()
        out = compute_jit(*args)
        jax.block_until_ready(out)
        telemetry.record_step_time(_time.perf_counter() - t0,
                                   steps=block_size)
        return out

    def timed_sync(*args):
        t0 = _time.perf_counter()
        out = sync_jit(*args)
        jax.block_until_ready(out)
        telemetry.record_sync_time(_time.perf_counter() - t0)
        return out

    return timed_compute, timed_sync


def dms_async_buffers_init(w_locals: jax.Array, topology: str):
    """Seed ``(sent, mixbuf)`` for the async carries and timed-sync path —
    the engine's zero-first-correction seed (one shared definition, see
    :func:`repro.core.sync.init_async_buffers`)."""
    from repro.core import sync as _sync
    return _sync.init_async_buffers(w_locals, topology)


# ---------------------------------------------------------------------------
# single-block stepper — the unit the overlap benchmark times and the
# jaxpr/HLO overlap test inspects
# ---------------------------------------------------------------------------

def dms_stepper_init(w0: jax.Array, workers: int, *, overlap: str = "none",
                     chunks: int = 4, topology: str = "all",
                     gossip_async: bool = False):
    """Global (stacked) initial carry for :func:`dms_block_stepper`."""
    d = w0.shape[0]
    wk = jnp.broadcast_to(w0, (workers, d))
    if gossip_async:
        sent, mixbuf = dms_async_buffers_init(wk, topology)
        carry = {"w": wk, "sent": sent, "mixbuf": mixbuf}
    elif overlap == "none":
        carry = {"w": wk}
    elif overlap == "delayed":
        carry = {"w": wk, "pending": jnp.zeros((workers, d), w0.dtype)}
    elif overlap == "chunked":
        dp = _padded_width(d, chunks)
        wp = jnp.zeros((workers, dp), w0.dtype).at[:, :d].set(wk)
        carry = {"w": wp, "cnt": jnp.zeros((), jnp.int32)}
    else:
        raise ValueError(f"unknown overlap mode: {overlap!r}")
    if _needs_round(overlap, topology):
        carry["cnt"] = jnp.zeros((), jnp.int32)
    return carry


def dms_block_stepper(mesh, axis: str, *, d: int, c: float = 1.0,
                      grad_impl: str = "jnp", overlap: str = "none",
                      chunks: int = 4, topology: str = "all",
                      gossip_async: bool = False):
    """One DMS block (compute + boundary sync) as a jittable step:

        step(carry, xblk, yblk, alpha) → carry

    with ``carry`` from :func:`dms_stepper_init` (leaves carry a leading
    worker dim sharded over ``axis``; ``cnt`` is replicated) and ``xblk``
    (K, bs, d) / ``yblk`` (K, bs) sharded over ``axis``. Not jitted — wrap
    in ``jax.jit``/``lax.scan`` for timing, or ``jax.make_jaxpr`` to verify
    the overlap property (delayed: no dot depends on the block's pmean), the
    gossip property (ring/pairwise: ppermutes only, no global collective),
    or the async property (``gossip_async``: the ppermute output feeds only
    the carried ``sent``/``mixbuf`` buffers — no dot in this *or* the next
    block consumes it).
    """
    blockfn = _make_worker_block(axis, c=c, grad_impl=grad_impl,
                                 overlap=overlap, chunks=chunks, d=d,
                                 topology=topology,
                                 gossip_async=gossip_async)
    cspec = {"w": P(axis)}
    if gossip_async:
        cspec["sent"] = P(axis)
        cspec["mixbuf"] = P(axis)
    if overlap == "delayed":
        cspec["pending"] = P(axis)
    if overlap == "chunked" or _needs_round(overlap, topology):
        cspec["cnt"] = P()

    def step(carry, xblk, yblk, alpha):
        def worker(carry, xw, yw):
            local = {k: (v if k == "cnt" else v[0]) for k, v in carry.items()}
            out = blockfn(local, xw[0], yw[0], alpha)
            return {k: (v if k == "cnt" else v[None]) for k, v in out.items()}
        f = jax.shard_map(worker, mesh=mesh,
                          in_specs=(cspec, P(axis), P(axis)),
                          out_specs=cspec,
                          axis_names={axis}, check_vma=False)
        return f(carry, xblk, yblk)

    return step


def dms_block_ladder(mesh, axis: str, *, d: int, workers: int, block_sizes,
                     c: float = 1.0, grad_impl: str = "jnp",
                     overlap: str = "none", chunks: int = 4,
                     topology: str = "all", gossip_async: bool = False,
                     dtype=jnp.float32):
    """Pre-compiled block-size ladder for the SVM path — the DMS analog of
    the LM trainer's H-ladder (:mod:`repro.runtime.ladder`).

    One :func:`dms_block_stepper` is traced once (its carry layout is
    block-size independent) and AOT-compiled for every ``bs`` in
    ``block_sizes``: ``{bs: rung}`` where ``rung(carry, xblk, yblk,
    alpha)`` expects ``xblk (K, bs, d)`` / ``yblk (K, bs)`` and can never
    retrace or recompile (a shape mismatch raises). Each rung is lowered
    with explicit shardings (worker-dim leaves over ``axis``, ``cnt`` and
    ``alpha`` replicated) and places its arguments there before the call,
    so a carry from :func:`dms_stepper_init` or :func:`dms_ladder_switch`
    is accepted wherever it lives. A mid-run MSF move is
    :func:`dms_ladder_switch` on the carry + picking another rung +
    re-blocking the data stream.
    """
    step = dms_block_stepper(mesh, axis, d=d, c=c, grad_impl=grad_impl,
                             overlap=overlap, chunks=chunks,
                             topology=topology, gossip_async=gossip_async)
    carry = dms_stepper_init(jnp.zeros((d,), dtype), workers,
                             overlap=overlap, chunks=chunks,
                             topology=topology, gossip_async=gossip_async)
    sharded, replicated = (NamedSharding(mesh, P(axis)),
                           NamedSharding(mesh, P()))
    carry_sh = {k: (replicated if k == "cnt" else sharded) for k in carry}
    jitted = jax.jit(step, in_shardings=(carry_sh, sharded, sharded,
                                         replicated),
                     out_shardings=carry_sh)
    carry_avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), carry)
    alpha_aval = jax.ShapeDtypeStruct((), dtype)

    def placed(compiled):
        def rung(*args):
            return compiled(*jax.device_put(args, compiled.input_shardings[0]))
        return rung

    out = {}
    for bs in sorted(set(int(b) for b in block_sizes)):
        x_aval = jax.ShapeDtypeStruct((workers, bs, d), dtype)
        y_aval = jax.ShapeDtypeStruct((workers, bs), dtype)
        out[bs] = placed(jitted.lower(carry_avals, x_aval, y_aval,
                                      alpha_aval).compile())
    return out


def dms_ladder_switch(carry, *, overlap: str = "none", chunks: int = 4,
                      topology: str = "all", gossip_async: bool = False,
                      d: Optional[int] = None):
    """Exact carry for resuming DMS at a different block size (host-level,
    stacked carry from :func:`dms_stepper_init`/:func:`dms_block_stepper`).

    Collapses the carry to the flushed model — delayed folds the pending
    correction first, then the worker mean (exact: workers are identical
    under blocking ``topology="all"``; within one block's drift under
    delayed; and the mean is the invariant consensus target under any
    gossip topology, chunked staleness included) — and re-seeds a fresh
    carry at that model via :func:`dms_stepper_init`. By construction the
    result is bit-identical to a fresh ladder start from the flushed
    weights, which is the ladder-switch exactness the tests assert.
    """
    wk = carry["w"].astype(jnp.float32)
    if overlap == "delayed":
        wk = wk + carry["pending"].astype(jnp.float32)
    w = jnp.mean(wk, axis=0)
    if overlap == "chunked" and d is not None:
        w = w[:d]
    workers = carry["w"].shape[0]
    return dms_stepper_init(w.astype(carry["w"].dtype), workers,
                            overlap=overlap, chunks=chunks,
                            topology=topology, gossip_async=gossip_async)
