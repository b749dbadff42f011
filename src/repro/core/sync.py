"""Model-synchronization engine — the paper's contribution as a library.

The paper's finding: the *frequency* of model synchronization (MSF) is a
free knob — accuracy is flat across block sizes while communication cost
scales as ``1/H`` — so sync schedule should be a first-class config, not an
implementation detail. This module turns :class:`repro.config.SyncConfig`
into the sync-point transformation applied inside the compiled train block:

    sync_point(params_start, params_end, sync_state, cfg, axis)
        → (new_params, new_sync_state)

Strategy × overlap matrix (all reduce over the *replica* mesh axis):

=================  ==========================================================
``strategy``       when the sync point runs
=================  ==========================================================
sync_every_step    never (XLA's data-parallel grad all-reduce every step;
                   the engine is bypassed — config completeness only)
periodic           every H local steps (paper's DMS): ``w ← mean_K(w_local)``
hierarchical       as periodic, but the replica axis is the *pod* (DCN) axis
                   while the intra-pod data axis still syncs every step
=================  ==========================================================

=================  ==========================================================
``overlap``        what the sync point does when it runs
=================  ==========================================================
none               blocking: ``w ← w_start + mean_K(Δ)`` at the boundary —
                   the paper's semantics, bit-exact DMS ≡ SRDMS
delayed            stale-by-one: block *i* computes ``mean_K(Δᵢ)`` but the
                   result is applied at the end of block *i+1*; this block's
                   params depend only on the *previous* mean, so the
                   collective is free to run under block *i+1*'s compute.
                   Each replica's params stay ``anchor + own latest Δ``;
                   divergence is bounded by one block's local drift
                   (Stich 2018's local-SGD staleness regime)
chunked            partial: the parameter tree is split into ``cfg.chunks``
                   byte-balanced shards (equal-size leaves round-robin) and
                   one shard is value-averaged per block
                   (``w_leaf ← mean_K(w_leaf)``); each leaf syncs every
                   ``chunks·H`` steps and per-sync wire bytes shrink
                   ``chunks``×
=================  ==========================================================

=================  ==========================================================
``topology``       which replicas one sync couples (composes with overlap)
=================  ==========================================================
all                global collective (``pmean``/``psum``/all-gather): exact
                   consensus per sync, but one straggler stalls all K
ring               gossip: two ``lax.ppermute`` neighbor exchanges,
                   ``w ← (w + w_left + w_right)/3``. O(1) neighbor bytes
                   per sync (independent of K), no global barrier;
                   disagreement contracts by λ₂(ring, K) per round
pairwise           gossip: rotating disjoint odd–even pairs average with
                   weight ½ (round parity alternates the pairing so the
                   whole ring mixes). Even replica count required; one
                   partner's bytes per sync
=================  ==========================================================

Gossip sync points exchange parameter *values*, not deltas: mixing is a
doubly stochastic contraction, so per-replica anchors cannot drift apart
and the replica mean is invariant — ``flush_overlap``'s replica average is
the exact consensus target. ``overlap="delayed"`` composes by carrying the
gossip correction ``mix(w) − w`` one block stale (the ppermute feeds only
the carried state, never this block's compute); ``"chunked"`` gossips one
byte-balanced shard per boundary. Compression composes point-to-point: the
wire carries the quantized payload plus a per-sender scale (no shared-scale
``pmax``, and no psum headroom — the full int range is usable).

``gossip_async=True`` (gossip topologies only) makes the rounds
*unsynchronized*: each replica mixes with the **last received** neighbor
snapshot instead of the current-round one — a double-buffered ``ppermute``
exchange that sends this boundary's params and consumes the buffer the
previous boundary filled (bounded staleness = 1 round on the compiled
path). The stale correction ``(M w̃)_i − w̃_i`` still applies a doubly
stochastic M to one common snapshot ``w̃``, so the corrections sum to zero
across replicas and the replica mean stays invariant — the exact flush is
unchanged. This boundary's ppermute output feeds only the carried buffers,
never any compute before the *next* boundary, so the exchange has an
entire block of slack — overlap modes are rejected as redundant (they
would compound staleness past the 1-round bound).

Optional modifiers (beyond-paper, composable):

* ``compression="int8"`` — error-feedback int8 delta exchange
  (:mod:`repro.core.compression`), shrinking the sync collective 4×.
* ``compression="int16"`` — fixed-point 2-byte all-reduce wire.
* ``slowmo > 0`` — outer momentum on the averaged delta (SlowMo, Wang et
  al.); composes with ``overlap="delayed"`` (the momentum step is taken on
  the freshly averaged delta, applied one block late) and with
  ``"chunked"`` via a per-shard momentum: each leaf carries an ``anchor``
  (its value after its own last slowmo step) and momentum-steps on
  ``mean_K(w_leaf) − anchor`` at the boundaries where it syncs (see
  ``_sync_point_chunked``). Gossip topologies still reject slowmo — they
  never materialize a global mean.

Byte accounting lives in :mod:`repro.core.costmodel` (shared with the MSF
auto-tuner so the two can never drift).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import SyncConfig
from repro.core import compression as C
from repro.core import costmodel
from repro.core.telemetry import scope


def needs_replica_axis(cfg: SyncConfig) -> bool:
    return cfg.strategy in ("periodic", "hierarchical")


def validate(cfg: SyncConfig) -> None:
    if cfg.overlap not in ("none", "delayed", "chunked"):
        raise ValueError(f"unknown overlap mode: {cfg.overlap!r}")
    if cfg.topology not in ("all", "ring", "pairwise"):
        raise ValueError(f"unknown sync topology: {cfg.topology!r}")
    if cfg.topology != "all" and cfg.slowmo > 0.0:
        raise ValueError("slowmo steps on the globally averaged delta; "
                         "gossip topologies never materialize a global mean")
    if cfg.gossip_async:
        if cfg.topology == "all":
            raise ValueError(
                "gossip_async is the unsynchronized-round gossip mode; it "
                "needs topology='ring' or 'pairwise' (a global collective "
                "has no per-neighbor buffer to double-buffer)")
        if cfg.overlap != "none":
            raise ValueError(
                "gossip_async already runs the exchange a full block ahead "
                "of its consumer (bounded staleness = 1 round); "
                f"overlap={cfg.overlap!r} would compound the staleness — "
                "use overlap='none'")
    if cfg.overlap == "chunked" and cfg.chunks < 1:
        raise ValueError(f"chunks must be >= 1, got {cfg.chunks}")
    if cfg.adaptive:
        if cfg.adapt_every < 1:
            raise ValueError(
                f"adapt_every must be >= 1, got {cfg.adapt_every}")
        if cfg.adapt_hysteresis < 0.0:
            raise ValueError("adapt_hysteresis must be >= 0, "
                             f"got {cfg.adapt_hysteresis}")
        if cfg.adapt_rung_hysteresis < 1:
            raise ValueError("adapt_rung_hysteresis must be >= 1, "
                             f"got {cfg.adapt_rung_hysteresis}")
        if cfg.adapt_h_max < 1:
            raise ValueError(f"adapt_h_max must be >= 1, "
                             f"got {cfg.adapt_h_max}")
        if any(h < 1 for h in cfg.adapt_ladder):
            raise ValueError(f"adapt_ladder rungs must be >= 1, "
                             f"got {cfg.adapt_ladder}")


def init_sync_state(cfg: SyncConfig, params) -> Dict[str, Any]:
    validate(cfg)
    state: Dict[str, Any] = {}
    zeros = lambda: jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if cfg.compression in ("int8", "int16"):
        state["ef"] = C.init_error_feedback(params)
    if cfg.slowmo > 0.0:
        state["slowmo_m"] = zeros()
    if cfg.overlap == "delayed":
        # pending correction = (averaged step delta − own local delta) of the
        # previous block; applied to this block's end params (stale-by-one)
        state["pending"] = zeros()
    if cfg.overlap == "chunked":
        state["chunk_idx"] = jnp.zeros((), jnp.int32)
        if cfg.slowmo > 0.0:
            # per-shard outer momentum needs a per-leaf reference: the value
            # this leaf held right after ITS last slowmo step (leaves sync on
            # different boundaries, so a whole-tree block anchor can't exist)
            state["anchor"] = jax.tree.map(
                lambda p: p.astype(jnp.float32), params)
    if cfg.gossip_async:
        # double buffers of the unsynchronized-round exchange: ``sent`` is
        # the snapshot this replica transmitted at its previous boundary,
        # ``mixbuf`` the neighbor-weighted payload sum Σ_{j≠i} M_ij w̃_j it
        # received there (see init_async_buffers for the zero-correction
        # seed invariant).
        state["sent"], state["mixbuf"] = init_async_buffers(params,
                                                            cfg.topology)
    if cfg.topology == "pairwise" and cfg.overlap != "chunked":
        # round parity selects the odd/even pairing (chunked derives the
        # round from chunk_idx instead — one counter per concern)
        state["gossip_round"] = jnp.zeros((), jnp.int32)
    return state


def sync_state_axes(cfg: SyncConfig, param_axes) -> Dict[str, Any]:
    """Logical-axes tree matching init_sync_state (mirrors params)."""
    state: Dict[str, Any] = {}
    if cfg.compression in ("int8", "int16"):
        state["ef"] = param_axes
    if cfg.slowmo > 0.0:
        state["slowmo_m"] = param_axes
    if cfg.overlap == "delayed":
        state["pending"] = param_axes
    if cfg.overlap == "chunked":
        state["chunk_idx"] = ()
        if cfg.slowmo > 0.0:
            state["anchor"] = param_axes
    if cfg.gossip_async:
        state["sent"] = param_axes
        state["mixbuf"] = param_axes
    if cfg.topology == "pairwise" and cfg.overlap != "chunked":
        state["gossip_round"] = ()
    return state


# ---------------------------------------------------------------------------
# the mean-exchange primitive (shared by every overlap mode)
# ---------------------------------------------------------------------------

def _gossip_perms(k: int, topology: str):
    """Static ppermute (source → dest) lists, one list per wire exchange.

    ``ring`` returns both neighbor shifts; ``pairwise`` returns the two
    alternating pairings (even rounds pair (0,1)(2,3)…, odd rounds
    (1,2)(3,4)…(K−1,0)) — the caller selects by round parity.
    """
    if topology == "ring":
        return [[(i, (i + 1) % k) for i in range(k)],
                [(i, (i - 1) % k) for i in range(k)]]
    if topology == "pairwise":
        if k % 2:
            raise ValueError(
                f"topology='pairwise' needs an even replica count, got {k}")
        even = [(i, i ^ 1) for i in range(k)]
        odd = [(i, (i - 1) % k if i % 2 == 0 else (i + 1) % k)
               for i in range(k)]
        return [even, odd]
    raise ValueError(f"unknown gossip topology: {topology!r}")


def _mix_with(self_val, send, k: int, topology: str, round_idx):
    """Topology-weighted combine of own payload with the neighbors'.

    ``send(perm)`` returns the ``ppermute``'d payload for one wire
    exchange — the single definition of the gossip weighting (ring thirds,
    pairwise halves with parity-``cond`` pairing) shared by the raw-value
    and compressed paths.
    """
    if k == 1:
        return self_val
    perms = _gossip_perms(k, topology)
    if topology == "ring":
        return (self_val + send(perms[0]) + send(perms[1])) / 3.0
    if round_idx is None:
        # a frozen pairing would "converge" each disjoint pair to its own
        # mean and never reach global consensus — refuse rather than mix
        # wrongly (every engine path threads a counter: gossip_round, or
        # chunk_idx // chunks under chunked)
        raise ValueError("topology='pairwise' alternates its pairing by "
                         "round; pass round_idx")
    def pair(perm):
        return lambda v: (v + send(perm)) / 2.0
    return jax.lax.cond(round_idx % 2 == 0, pair(perms[0]), pair(perms[1]),
                        self_val)


def gossip_self_weight(topology: str) -> float:
    """Diagonal ``M_ii`` of the gossip mixing matrix (same for every i):
    ring thirds, pairwise halves. The async double buffer splits the mix
    into ``M_ii·own + Σ_{j≠i} M_ij·recv`` — this is the own-term weight."""
    if topology == "ring":
        return 1.0 / 3.0
    if topology == "pairwise":
        return 0.5
    raise ValueError(f"unknown gossip topology: {topology!r}")


def _recv_with(send, k: int, topology: str, round_idx):
    """Neighbor-weighted payload sum ``Σ_{j≠i} M_ij x_j`` — the receive
    half of one wire exchange (no self term). ``_mix_with`` ≡
    ``self_weight·own + _recv_with`` for the synchronous path; the async
    path banks this in ``mixbuf`` and consumes it one boundary later.
    """
    perms = _gossip_perms(k, topology)
    if topology == "ring":
        return (send(perms[0]) + send(perms[1])) / 3.0
    if round_idx is None:
        raise ValueError("topology='pairwise' alternates its pairing by "
                         "round; pass round_idx")
    def pair(perm):
        return lambda _: send(perm) / 2.0
    return jax.lax.cond(round_idx % 2 == 0, pair(perms[0]), pair(perms[1]),
                        0.0)


def gossip_mix(x, axis: str, topology: str, round_idx=None):
    """Mix one (uncompressed) array with its topology neighbors over
    ``axis`` — the doubly stochastic gossip step ``x ← Σ_j M_ij x_j``.

    Must run inside shard_map with ``axis`` manual. ``round_idx`` (traced
    i32) selects the pairwise round parity — required for ``pairwise``,
    ignored by ``ring``. The only collectives emitted are ``ppermute``s —
    no global barrier.
    """
    k = jax.lax.psum(1, axis)      # static at trace time
    return _mix_with(x, lambda perm: jax.lax.ppermute(x, axis, perm),
                     k, topology, round_idx)


def _gossip_exchange(values, ef, cfg: SyncConfig, axis: str, round_idx):
    """Neighbor-mixed pytree under ``cfg.topology``/``cfg.compression``.

    Returns ``(mixed_tree, new_ef_tree_or_None)`` like :func:`_exchange_mean`
    but moves only point-to-point ``ppermute`` payloads — no global
    collective. Compressed wires carry ``(q, per-sender scale)`` pairs and
    every replica mixes its *own dequantized* payload (not the raw value),
    so the mixing matrix stays doubly stochastic over what was actually
    transmitted; the quantization residual goes to error feedback.
    """
    k = jax.lax.psum(1, axis)      # static at trace time

    if cfg.compression in ("int8", "int16"):
        qmax, qdtype = ((127, jnp.int8) if cfg.compression == "int8"
                        else (32767, jnp.int16))

        def leaf(v, e):
            val = v.astype(jnp.float32) + e
            amax = jnp.max(jnp.abs(val))
            scale = jnp.maximum(amax, 1e-12) / qmax
            q = jnp.clip(jnp.round(val / scale), -qmax, qmax).astype(qdtype)
            deq_self = q.astype(jnp.float32) * scale

            def send(perm):
                qn = jax.lax.ppermute(q, axis, perm)
                sn = jax.lax.ppermute(scale, axis, perm)
                return qn.astype(jnp.float32) * sn

            return (_mix_with(deq_self, send, k, cfg.topology, round_idx),
                    val - deq_self)

        out = jax.tree.map(leaf, values, ef)
        is_t = lambda x: isinstance(x, tuple)
        mixed = jax.tree.map(lambda o: o[0], out, is_leaf=is_t)
        new_ef = jax.tree.map(lambda o: o[1], out, is_leaf=is_t)
        return mixed, new_ef

    def leaf(v):
        return gossip_mix(v.astype(jnp.float32), axis, cfg.topology,
                          round_idx)

    return jax.tree.map(leaf, values), None


def init_async_buffers(params, topology: str):
    """Seed ``(sent, mixbuf)`` for the async double buffers from a params
    pytree: as if every replica had transmitted its current model at a
    previous boundary, so when replicas start identical the first stale
    correction ``mixbuf + (M_ii−1)·sent`` is exactly zero. The single
    definition of the seed — init, resume (``local_sgd.finalize_state``)
    and the SVM carries all call it, so they cannot drift.
    """
    w_self = gossip_self_weight(topology)
    # at least f32 (bf16 params get f32 buffers) without downcasting an
    # f64 carry — lax.scan needs the carry dtype stable across boundaries
    sent = jax.tree.map(
        lambda p: p.astype(jnp.promote_types(p.dtype, jnp.float32)), params)
    mixbuf = jax.tree.map(lambda p: (1.0 - w_self) * p, sent)
    return sent, mixbuf


def gossip_recv(x, axis: str, topology: str, round_idx=None):
    """Receive half of one gossip exchange over ``axis``: the
    neighbor-weighted payload sum ``Σ_{j≠i} M_ij x_j`` (ppermutes only, no
    self term). ``gossip_mix(x) ≡ gossip_self_weight·x + gossip_recv(x)``;
    the async path banks this in its ``mixbuf`` double buffer instead of
    consuming it at the same boundary.
    """
    k = jax.lax.psum(1, axis)      # static at trace time
    return _recv_with(lambda perm: jax.lax.ppermute(x, axis, perm),
                      k, topology, round_idx)


def _gossip_async_exchange(values, ef, cfg: SyncConfig, axis: str,
                           round_idx):
    """Double-buffered half-exchange: ppermute this boundary's payload and
    return what lands in the buffers, to be *consumed at the next boundary*.

    Returns ``(recv_tree, sent_tree, new_ef_tree_or_None)``: ``recv`` is
    the neighbor-weighted payload sum ``Σ_{j≠i} M_ij p_j`` under this
    round's pairing and ``sent`` the own transmitted payload. Under
    compression the wire carries ``(q, per-sender scale)`` and ``sent`` is
    the own *dequantized* payload — every replica's stale mix then applies
    the doubly stochastic M to the same transmitted snapshot, and the
    quantization residual goes to error feedback.
    """
    k = jax.lax.psum(1, axis)      # static at trace time

    if cfg.compression in ("int8", "int16"):
        qmax, qdtype = ((127, jnp.int8) if cfg.compression == "int8"
                        else (32767, jnp.int16))

        def leaf(v, e):
            val = v + e
            amax = jnp.max(jnp.abs(val))
            scale = jnp.maximum(amax, 1e-12) / qmax
            q = jnp.clip(jnp.round(val / scale), -qmax, qmax).astype(qdtype)
            deq_self = q.astype(jnp.float32) * scale

            def send(perm):
                qn = jax.lax.ppermute(q, axis, perm)
                sn = jax.lax.ppermute(scale, axis, perm)
                return qn.astype(jnp.float32) * sn

            return (_recv_with(send, k, cfg.topology, round_idx),
                    deq_self, val - deq_self)

        out = jax.tree.map(leaf, values, ef)
        is_t = lambda x: isinstance(x, tuple)
        recv = jax.tree.map(lambda o: o[0], out, is_leaf=is_t)
        sent = jax.tree.map(lambda o: o[1], out, is_leaf=is_t)
        new_ef = jax.tree.map(lambda o: o[2], out, is_leaf=is_t)
        return recv, sent, new_ef

    def leaf(v):
        return _recv_with(lambda perm: jax.lax.ppermute(v, axis, perm),
                          k, cfg.topology, round_idx)

    return jax.tree.map(leaf, values), values, None


def _exchange_mean(values, ef, cfg: SyncConfig, axis: str, param_axes,
                   round_idx=None):
    """Replica exchange of a pytree over ``axis`` under cfg.compression.

    ``topology="all"`` returns the exact replica mean (global collective);
    gossip topologies return the neighbor-mixed values (``round_idx``
    selects the pairwise pairing). Returns ``(tree, new_ef_tree_or_None)``.
    ``values`` may be deltas (blocking/delayed under "all") or raw parameter
    values (chunked, and always under gossip); error feedback carries the
    quantization residual either way.
    """
    if cfg.topology != "all":
        return _gossip_exchange(values, ef, cfg, axis, round_idx)
    if cfg.compression == "int8":
        q, s, new_ef = C.compress_tree(values, ef)
        return C.allgather_mean_dequant(q, s, axis, param_axes), new_ef
    if cfg.compression == "int16":
        # fixed-point 2-byte wire via an ordinary (shape-preserving)
        # all-reduce: a psum of int16 composes cleanly with auto-axis
        # sharding, where the int8 all-gather materializes full leaves
        # per device and a bf16 pmean trips XLA's AllReducePromotion
        # CHECK (§Perf C-cell log). A shared per-tensor scale is agreed
        # via pmax first; ⌊log₂(32767/K)⌋ mantissa bits still beat bf16's
        # 8 at the same wire width for any realistic replica count.
        # Rounding error is carried in the EF buffer.
        k = jax.lax.psum(1, axis)          # static at trace time
        # headroom scales with the replica count so the int16 psum cannot
        # overflow: K·qmax ≤ 32767 (the old fixed ±8192 clip wrapped at
        # world ≥ 4 — 4·8192 = 32768 > int16 max)
        qmax = 32767 // k

        def int16_leaf(d, e):
            v = d + e
            amax = jax.lax.pmax(jnp.max(jnp.abs(v)), axis)
            scale = jnp.maximum(amax, 1e-12) / qmax
            q = jnp.clip(jnp.round(v / scale), -qmax, qmax
                         ).astype(jnp.int16)
            summed = jax.lax.psum(q, axis).astype(jnp.float32)
            mean = summed * scale / k
            return mean, v - q.astype(jnp.float32) * scale
        out = jax.tree.map(int16_leaf, values, ef)
        is_t = lambda x: isinstance(x, tuple)
        mean = jax.tree.map(lambda o: o[0], out, is_leaf=is_t)
        new_ef = jax.tree.map(lambda o: o[1], out, is_leaf=is_t)
        return mean, new_ef
    return jax.tree.map(lambda d: jax.lax.pmean(d, axis), values), None


def _slowmo_step(mean_delta, sync_state, new_state, cfg: SyncConfig):
    """Outer momentum on the averaged delta; returns the applied delta."""
    if cfg.slowmo <= 0.0:
        return mean_delta
    m = jax.tree.map(lambda mm, d: cfg.slowmo * mm + d,
                     sync_state["slowmo_m"], mean_delta)
    new_state["slowmo_m"] = m
    return jax.tree.map(lambda mm: cfg.slowmo_lr * mm, m)


def _f32_delta(params_end, params_start):
    return jax.tree.map(
        lambda e, s: e.astype(jnp.float32) - s.astype(jnp.float32),
        params_end, params_start)


def _apply_f32(params, delta):
    return jax.tree.map(
        lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
        params, delta)


# ---------------------------------------------------------------------------
# sync point — one call per block boundary
# ---------------------------------------------------------------------------

def sync_point(params_start, params_end, sync_state: Dict[str, Any],
               cfg: SyncConfig, axis: str,
               param_axes=None) -> Tuple[Any, Dict[str, Any]]:
    """One model synchronization, inside shard_map with ``axis`` manual.

    ``params_start`` — the params the block started from (identical across
    replicas for ``overlap="none"``; per-replica under delayed/chunked and
    any gossip topology); ``params_end`` — this replica's drifted params.
    ``param_axes`` — per-leaf logical axes (keeps the compressed-sync
    buffers sharded; see compression.allgather_mean_dequant).
    """
    with scope("lm.sync"):
        if cfg.gossip_async:
            return _sync_point_gossip_async(params_end, sync_state, cfg, axis)
        if cfg.topology != "all" and cfg.overlap != "chunked":
            return _sync_point_gossip(params_end, sync_state, cfg, axis)
        if cfg.overlap == "delayed":
            return _sync_point_delayed(params_start, params_end, sync_state,
                                       cfg, axis, param_axes)
        if cfg.overlap == "chunked":
            return _sync_point_chunked(params_end, sync_state, cfg, axis,
                                       param_axes)

        delta = _f32_delta(params_end, params_start)
        new_state = dict(sync_state)
        mean_delta, new_ef = _exchange_mean(delta, sync_state.get("ef"), cfg,
                                            axis, param_axes)
        if new_ef is not None:
            new_state["ef"] = new_ef
        step_delta = _slowmo_step(mean_delta, sync_state, new_state, cfg)
        return _apply_f32(params_start, step_delta), new_state


def _sync_point_delayed(params_start, params_end, sync_state, cfg, axis,
                        param_axes):
    """Stale-by-one averaging: launch this block's mean, apply last block's.

    The returned params depend only on ``sync_state["pending"]`` (computed
    at the *previous* boundary), never on this boundary's collective — so in
    the compiled schedule the collective's first consumer is the *next*
    block's sync tail and XLA is free to run it under that block's compute.
    Replica k's params stay ``anchor + own latest local delta``; applying
    ``pending = mean_{i−1} − Δ_{i−1,k}`` swaps the stale local delta for its
    average, keeping divergence bounded by one block's drift.
    """
    delta = _f32_delta(params_end, params_start)
    new_state = dict(sync_state)
    mean_delta, new_ef = _exchange_mean(delta, sync_state.get("ef"), cfg,
                                        axis, param_axes)
    if new_ef is not None:
        new_state["ef"] = new_ef
    step_delta = _slowmo_step(mean_delta, sync_state, new_state, cfg)
    # apply the PREVIOUS boundary's correction to this block's end params
    new_params = _apply_f32(params_end, sync_state["pending"])
    new_state["pending"] = jax.tree.map(lambda m, d: m - d, step_delta, delta)
    return new_params, new_state


def _sync_point_gossip(params_end, sync_state, cfg, axis):
    """Gossip sync (ring/pairwise): mix parameter *values* with neighbors.

    Value form (``w ← Σ_j M_ij w_j``, not a delta exchange) because gossip
    never re-establishes a common anchor: a delta-only exchange would let
    the per-replica anchors drift apart unboundedly, while value mixing
    contracts the whole disagreement by λ₂ per round and keeps the replica
    mean invariant (M is doubly stochastic).

    ``overlap="none"`` applies the mixed values at this boundary (blocking
    on two ppermutes — still no global barrier). ``overlap="delayed"``
    carries the gossip correction ``mix(w) − w`` one block stale: this
    boundary's ppermute output feeds only ``pending``, so the exchange is
    free to run under the next block's compute.
    """
    new_state = dict(sync_state)
    rnd = sync_state.get("gossip_round")
    if rnd is not None:
        new_state["gossip_round"] = rnd + 1
    vals = jax.tree.map(lambda p: p.astype(jnp.float32), params_end)
    mixed, new_ef = _gossip_exchange(vals, sync_state.get("ef"), cfg, axis,
                                     rnd)
    if new_ef is not None:
        new_state["ef"] = new_ef
    if cfg.overlap == "delayed":
        new_params = _apply_f32(params_end, sync_state["pending"])
        new_state["pending"] = jax.tree.map(lambda m, v: m - v, mixed, vals)
        return new_params, new_state
    new_params = jax.tree.map(lambda m, p: m.astype(p.dtype), mixed,
                              params_end)
    return new_params, new_state


def _sync_point_gossip_async(params_end, sync_state, cfg, axis):
    """Asynchronous (unsynchronized-round) gossip: mix with the *last
    received* neighbor snapshot instead of the current-round one.

    The correction applied at this boundary is ``(M w̃)_i − w̃_i`` where
    ``w̃`` is the snapshot every replica transmitted at its PREVIOUS
    boundary — reconstructed from the double buffers as
    ``mixbuf + M_ii·sent − sent``. M is doubly stochastic and applies to
    one common snapshot, so the corrections sum to zero over replicas and
    the replica mean stays invariant (exact flush unchanged). This
    boundary then transmits the *post-correction* params: with zero local
    drift the recurrence collapses to synchronous gossip one round behind
    (``w_t = M w_{t−1}``), so the per-round contraction is still λ₂ — what
    staleness costs is one extra block of unmixed drift, which the
    auto-tuner charges via ``costmodel.effective_spectral_gap``.

    Schedule-wise this is stronger than ``overlap="delayed"``: the
    ppermute output feeds only the carried buffers, and nothing before the
    *next* boundary reads them — the exchange has an entire block of slack
    and a replica never waits for a neighbor's current round.
    """
    new_state = dict(sync_state)
    rnd = sync_state.get("gossip_round")
    if rnd is not None:
        new_state["gossip_round"] = rnd + 1
    w_self = gossip_self_weight(cfg.topology)
    vals = jax.tree.map(lambda p: p.astype(jnp.float32), params_end)
    new_w = jax.tree.map(
        lambda v, rb, s: v + rb + (w_self - 1.0) * s,
        vals, sync_state["mixbuf"], sync_state["sent"])
    recv, sent, new_ef = _gossip_async_exchange(
        new_w, sync_state.get("ef"), cfg, axis, rnd)
    new_state["mixbuf"] = recv
    new_state["sent"] = sent
    if new_ef is not None:
        new_state["ef"] = new_ef
    new_params = jax.tree.map(lambda m, p: m.astype(p.dtype), new_w,
                              params_end)
    return new_params, new_state


def chunk_assignment(leaves, chunks: int):
    """Leaf index → shard id, byte-balanced (greedy largest-first onto the
    lightest shard; ties broken by leaf order, so equal-size leaves land
    round-robin). Balancing by *bytes* — ``size · dtype.itemsize``, not
    element count, so mixed-precision trees (bf16 params + fp32 buffers)
    balance by what actually crosses the wire — is what makes the cost
    model's per-sync ``/chunks`` accounting hold for skewed trees; a
    leaf-count round-robin would let one shard carry the whole embedding
    table. A single leaf larger than total/chunks still bounds the worst
    boundary from below (no intra-leaf splitting here)."""
    def nbytes(leaf):
        return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    order = sorted(range(len(leaves)),
                   key=lambda i: (-nbytes(leaves[i]), i))
    load = [0] * max(1, chunks)
    assign = [0] * len(leaves)
    for i in order:
        s = min(range(len(load)), key=lambda rr: (load[rr], rr))
        assign[i] = s
        load[s] += nbytes(leaves[i])
    return assign


def _sync_point_chunked(params_end, sync_state, cfg, axis, param_axes):
    """Value-average one shard of the tree per boundary.

    ``params_start`` is irrelevant: a chunked leaf may not have synced for
    ``chunks`` blocks, so its replicas' block-start values already diverge —
    consistency is re-established from the *end* values (``mean_K(w)``).
    ``lax.switch`` keys the traced ``chunk_idx`` (replicated state, so every
    replica takes the same branch) into per-shard branches; only the taken
    branch's collective executes, so one boundary moves ~1/chunks of the
    tree's bytes (shards are byte-balanced — see chunk_assignment). Under a
    gossip topology the shard is neighbor-mixed instead of globally
    averaged; the pairwise round parity advances once per full round-robin
    pass (``chunk_idx // chunks``) so each leaf alternates pairings across
    its own syncs.

    ``slowmo > 0`` composes via a PER-SHARD outer momentum: each leaf keeps
    an ``anchor`` (its value right after its own last slowmo step) and a
    momentum buffer, and this boundary's synced leaves step

        m ← β·m + (mean_K(w_leaf) − anchor);  w_leaf ← anchor + lr_out·m

    with the anchor advanced to the new value. Leaves sync on different
    boundaries, so a whole-tree block delta never exists — the per-leaf
    anchor supplies the reference the blocking/delayed paths get from
    ``params_start``. For ``chunks=1`` (anchor ≡ block start, mean of ends
    ≡ start + meanΔ) this reduces exactly to the blocking slowmo step.
    """
    r = max(1, cfg.chunks)
    idx = sync_state["chunk_idx"]
    ef = sync_state.get("ef")
    have_ef = ef is not None
    slowmo = cfg.slowmo > 0.0
    mom = sync_state.get("slowmo_m") if slowmo else None
    anchor = sync_state.get("anchor") if slowmo else None
    ax_leaves = (jax.tree.leaves(
        param_axes, is_leaf=lambda x: x is None or isinstance(x, tuple))
        if param_axes is not None
        else [None] * len(jax.tree.leaves(params_end)))
    assign = chunk_assignment(jax.tree.leaves(params_end), r)

    def make_branch(rr):
        def branch(operands):
            p_end, ef_in, m_in, a_in = operands
            leaves, treedef = jax.tree.flatten(p_end)
            ef_leaves = (jax.tree.leaves(ef_in) if have_ef
                         else [None] * len(leaves))
            m_leaves = jax.tree.leaves(m_in) if slowmo else None
            a_leaves = jax.tree.leaves(a_in) if slowmo else None
            # shard-rr leaf subset as {leaf_index: value} dict pytrees
            sub = [i for i in range(len(leaves)) if assign[i] == rr]
            vals = {i: leaves[i].astype(jnp.float32) for i in sub}
            efs = {i: ef_leaves[i] for i in sub} if have_ef else None
            axs = {i: ax_leaves[i] for i in sub}
            mean, new_ef = _exchange_mean(vals, efs, cfg, axis, axs,
                                          round_idx=idx // r)
            new_leaves = list(leaves)
            new_ef_leaves = list(ef_leaves)
            new_m = list(m_leaves) if slowmo else None
            new_a = list(a_leaves) if slowmo else None
            for i in sub:
                if slowmo:
                    m = cfg.slowmo * m_leaves[i] + (mean[i] - a_leaves[i])
                    w_new = a_leaves[i] + cfg.slowmo_lr * m
                    new_m[i] = m
                    new_a[i] = w_new
                    new_leaves[i] = w_new.astype(leaves[i].dtype)
                else:
                    new_leaves[i] = mean[i].astype(leaves[i].dtype)
                if have_ef and new_ef is not None:
                    new_ef_leaves[i] = new_ef[i]
            out_p = jax.tree.unflatten(treedef, new_leaves)
            out_ef = (jax.tree.unflatten(treedef, new_ef_leaves)
                      if have_ef else ef_in)
            out_m = jax.tree.unflatten(treedef, new_m) if slowmo else m_in
            out_a = jax.tree.unflatten(treedef, new_a) if slowmo else a_in
            return out_p, out_ef, out_m, out_a
        return branch

    operands = (params_end, ef, mom, anchor)
    new_params, new_ef, new_m, new_anchor = jax.lax.switch(
        idx % r, [make_branch(rr) for rr in range(r)], operands)
    new_state = dict(sync_state)
    new_state["chunk_idx"] = idx + 1
    if have_ef:
        new_state["ef"] = new_ef
    if slowmo:
        new_state["slowmo_m"] = new_m
        new_state["anchor"] = new_anchor
    return new_params, new_state


def flush_overlap(params, sync_state, cfg: SyncConfig, replica_dim: int = 0):
    """Collapse overlap staleness to the fully synchronized model.

    ``params``/``sync_state`` in the local-SGD stacked layout (leading
    replica dim). Under ``delayed`` each replica sits at ``anchor + ownΔ``
    with ``pending = stepΔ − ownΔ``, so ``params + pending`` is
    ``anchor + stepΔ`` on every replica — the model with every sync applied,
    *including* the slowmo momentum term inside stepΔ (a bare replica mean
    would drop it). ``chunked`` replicas differ only by not-yet-synced drift
    whose replica average is the consistent model; gossip topologies leave
    replicas within the geometric consensus envelope whose replica average
    is the invariant mean (doubly stochastic mixing); under
    ``gossip_async`` the in-flight buffer corrections sum to zero across
    replicas, so the bare replica mean is already the consensus target
    (``finalize_state`` re-seeds the double buffers from the flushed
    params so resume starts with a zero stale correction). When ``compression``
    is on, the error-feedback residual — quantization error each replica
    would have re-submitted at its next sync, where averaging would have
    spread its replica mean to everyone — is folded in before the collapse,
    so a checkpoint-resume from the flushed state neither loses nor
    double-counts the carried error (``finalize_state`` zeroes the EF
    buffer to match). Call before checkpointing/evaluating a state trained
    with ``overlap != "none"`` or ``topology != "all"`` (see
    local_sgd.finalize_state). Returns the stacked layout with all replicas
    equal.
    """
    if cfg.overlap == "none" and cfg.topology == "all":
        return params
    if cfg.overlap == "delayed":
        params = jax.tree.map(
            lambda p, q: (p.astype(jnp.float32) + q).astype(p.dtype),
            params, sync_state["pending"])
    if "ef" in sync_state:
        params = jax.tree.map(
            lambda p, e: (p.astype(jnp.float32) + e).astype(p.dtype),
            params, sync_state["ef"])

    def leaf(p):
        m = jnp.mean(p.astype(jnp.float32), axis=replica_dim, keepdims=True)
        return jnp.broadcast_to(m, p.shape).astype(p.dtype)
    return jax.tree.map(leaf, params)


# ---------------------------------------------------------------------------
# analytic byte accounting (delegates to the shared cost module)
# ---------------------------------------------------------------------------

def collective_bytes_per_sync(param_bytes: int, world: int,
                              cfg: SyncConfig) -> int:
    """Analytic wire bytes of one executed sync (napkin math / benchmarks).

    Single source of truth: :func:`repro.core.costmodel.wire_bytes_per_sync`
    (the MSF auto-tuner reads the same function).
    """
    return int(costmodel.wire_bytes_per_sync(param_bytes, world, cfg))


def amortized_bytes_per_step(param_bytes: int, world: int, cfg: SyncConfig) -> float:
    if cfg.strategy == "sync_every_step":
        return costmodel.wire_bytes_per_sync(param_bytes, world, cfg)
    return costmodel.wire_bytes_per_sync(param_bytes, world, cfg) / max(1, cfg.period)
