"""Host-side timing telemetry, and the names that profiler traces carry.

Names. Device scopes and host spans take their names from ``SCOPES`` and
``SPANS``, all prefixed ``repro.``, and only through :func:`scope`,
:func:`span` and :func:`step_span`:

* :func:`scope` is a ``jax.named_scope``: every HLO operation traced inside
  it carries ``repro.<name>`` as one component of its ``op_name`` path.
  Transforms wrap the path, they do not replace it: a backward or remat
  op reads ``jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/
  repro.lm.attention/dot_general``. A fusion carries its root's op_name.
  Scopes are metadata only: the compiled program is the same without them.
* :data:`ATTENTION` counts, while a program is traced, the training /
  prefill attention calls by the path each takes (``flash``, the fused
  TPU kernel; ``chunked`` and ``full``, the jnp paths; ``pallas``, the
  forward-only kernel a caller selects). A layer scan traces its body
  once; the scan counts it once per layer (:meth:`PathCounts.repeated`).
  :data:`SSD` counts the Mamba-2 mixer's SSD calls the same way, by path
  (``chunked``, the jnp chunked scan of ``models/ssm.py``).
* :func:`span` and :func:`step_span` are ``jax.profiler`` annotations on
  the profiler's host clock, which cost next to nothing while no trace is
  being taken. :class:`repro.runtime.ft.StepRunner` wraps each step in
  ``repro.step`` (its ``step_num`` is the identifier the step's spans
  share) and the feed, dispatch, metric fetch, save and ladder hook in
  spans of their own.

Timers. The MSF auto-tuner (:mod:`repro.core.autotune`) needs two numbers per
(model × mesh × fabric): ``T_step`` (compute time per optimizer step) and
``T_sync`` (one executed sync collective). This module collects both from
the *running* trainer — jitted code cannot time itself, so the timers wrap
the host-side step invocations (``jax.block_until_ready`` boundaries):

* the SVM timed-step path (``svm.dms_timed_steps``) measures compute and
  sync separately → :meth:`BlockTelemetry.record_step_time` /
  :meth:`record_sync_time` feed the EMAs directly;
* the LM block path (``local_sgd.make_train_step``) only sees whole-block
  wall times ``T(H) = H·T_step + T_sync`` → :meth:`record_block` keeps a
  per-H EMA and, once two distinct H's have been observed (the adaptive
  controller's H moves provide them), solves the two-parameter model by
  least squares on ``y = T_step + T_sync·(1/H)``.

The first sample of each kind is dropped (``warmup``) so jit compilation
never poisons the EMAs. All state is plain Python floats — safe to read
from the training loop at any block boundary.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple

import jax

PREFIX = "repro."
# device scopes: the SVM's local block update and its model exchange; the
# LM's attention (projections through ``wo``), MLP, cross-entropy,
# optimizer update and replica sync; the Mamba-2 mixer (projections, conv,
# gated norm, out-projection) and, nested in it, its chunked SSD scan
SCOPES = ("svm.block", "svm.sync", "lm.attention", "lm.mlp", "lm.loss",
          "lm.optimizer", "lm.sync", "lm.ssm", "lm.ssd")
# host spans of the step loop: ``step`` encloses one step's others
SPANS = ("step", "data", "dispatch", "fetch", "save", "ladder", "restore")


def scope(name: str):
    """``jax.named_scope("repro." + name)`` for a name in ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; add it to SCOPES")
    return jax.named_scope(PREFIX + name)


def span(name: str, **ids):
    """A host span ``repro.<name>`` (a name in ``SPANS``) on the profiler's
    clock; ``ids`` become the event's stats."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; add it to SPANS")
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def step_span(step: int):
    """The ``repro.step`` span of one training step, numbered ``step``."""
    return jax.profiler.StepTraceAnnotation(PREFIX + "step", step_num=step)


class PathCounts:
    """Trace-time count of calls by the path each took.

    :meth:`add` runs in the Python body of a traced function, so it counts
    traces, not executions. Inside :meth:`repeated` each call counts ``n``
    times: a ``lax.scan`` over ``n`` layers traces its body once.
    """

    def __init__(self, paths: Tuple[str, ...]):
        self.paths = paths
        self._counts = dict.fromkeys(paths, 0)
        self._times = 1

    def add(self, path: str) -> None:
        if path not in self._counts:
            raise ValueError(f"unknown path {path!r}; one of {self.paths}")
        self._counts[path] += self._times

    @contextlib.contextmanager
    def repeated(self, n: int):
        """Count each call inside as ``n`` calls."""
        prev = self._times
        self._times = prev * n
        try:
            yield
        finally:
            self._times = prev

    def counts(self) -> Dict[str, int]:
        return dict(self._counts)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        """Calls counted since ``before`` (an earlier :meth:`counts`)."""
        return {p: n - before.get(p, 0) for p, n in self._counts.items()}


# attention calls by path (``models/attention.py::full_attention``)
ATTENTION = PathCounts(("flash", "chunked", "full", "pallas"))
# SSD calls by path (``models/ssm.py::mamba_fwd``)
SSD = PathCounts(("chunked",))


class EMA:
    """Exponential moving average; ``None`` until the first update."""

    def __init__(self, decay: float = 0.8):
        self.decay = decay
        self.value: Optional[float] = None

    def update(self, x: float) -> float:
        self.value = (x if self.value is None
                      else self.decay * self.value + (1 - self.decay) * x)
        return self.value


class BlockTelemetry:
    """Measured ``T_step`` / ``T_sync`` estimates from the timed paths."""

    def __init__(self, decay: float = 0.8, warmup: int = 1):
        self._decay = decay
        self._step = EMA(decay)
        self._sync = EMA(decay)
        self._skip_step = warmup
        self._skip_sync = warmup
        self._skip_block = warmup
        self._block_by_h: Dict[int, EMA] = {}   # H → per-STEP wall-time EMA
        self._block_n_by_h: Dict[int, int] = {}  # H → recorded block count
        self.n_steps = 0
        self.n_syncs = 0
        self.n_blocks = 0

    # ------------------------------------------------------------ direct
    def record_step_time(self, seconds: float, steps: int = 1) -> None:
        """Measured compute-only time of ``steps`` optimizer steps."""
        if self._skip_step > 0:
            self._skip_step -= 1
            return
        self._step.update(seconds / max(1, steps))
        self.n_steps += steps

    def record_sync_time(self, seconds: float) -> None:
        """Measured time of one executed sync collective."""
        if self._skip_sync > 0:
            self._skip_sync -= 1
            return
        self._sync.update(seconds)
        self.n_syncs += 1

    # ----------------------------------------------------------- blocks
    def record_block(self, h: int, block_s: float,
                     sync_s: Optional[float] = None) -> None:
        """One whole sync block (H steps + boundary sync) of wall time.

        With a separately measured ``sync_s`` the split is exact; without
        it the (H, per-step time) pair feeds the least-squares separation.
        """
        if self._skip_block > 0:
            self._skip_block -= 1
            return
        self.n_blocks += 1
        h = max(1, int(h))
        self._block_n_by_h[h] = self._block_n_by_h.get(h, 0) + 1
        if sync_s is not None:
            self._sync.update(sync_s)
            self.n_syncs += 1
            self._step.update(max(block_s - sync_s, 0.0) / h)
            self.n_steps += h
            return
        self._block_by_h.setdefault(h, EMA(self._decay)).update(block_s / h)

    def _solve_blocks(self) -> Optional[Tuple[float, float]]:
        """Least squares of ``y = T_step + T_sync·x`` over x = 1/H."""
        pts = [(1.0 / h, e.value) for h, e in self._block_by_h.items()
               if e.value is not None]
        if len(pts) < 2:
            return None
        n = len(pts)
        sx = sum(x for x, _ in pts)
        sy = sum(y for _, y in pts)
        sxx = sum(x * x for x, _ in pts)
        sxy = sum(x * y for x, y in pts)
        den = n * sxx - sx * sx
        if abs(den) < 1e-18:
            return None
        t_sync = (n * sxy - sx * sy) / den
        t_step = (sy - t_sync * sx) / n
        return max(t_step, 0.0), max(t_sync, 0.0)

    # ---------------------------------------------------------- reading
    def estimates(self) -> Optional[Tuple[float, float]]:
        """(T_step, T_sync) in seconds, or None until enough data."""
        if self._step.value is not None and self._sync.value is not None:
            return self._step.value, self._sync.value
        return self._solve_blocks()

    def per_step_s(self) -> Optional[float]:
        """Crude per-step wall time when the split is underdetermined:
        the direct T_step EMA if one exists, else the mean of the per-H
        block EMAs (sync amortized in — an upper bound on T_step)."""
        if self._step.value is not None:
            return self._step.value
        vals = [e.value for e in self._block_by_h.values()
                if e.value is not None]
        return sum(vals) / len(vals) if vals else None

    def per_rung(self) -> Dict[int, dict]:
        """Per-H block stats — the H-ladder runtime's rung telemetry.

        ``per_step_s`` is the rung's whole-block wall time divided by H
        (sync amortized in); ``blocks`` how many blocks ran at that rung.
        Rungs observed only through the direct (separately timed) path
        report counts without a per-step EMA.
        """
        out: Dict[int, dict] = {}
        for h in sorted(self._block_n_by_h):
            ema = self._block_by_h.get(h)
            out[h] = {
                "per_step_s": ema.value if ema is not None else None,
                "blocks": self._block_n_by_h[h],
            }
        return out

    def to_dict(self) -> dict:
        est = self.estimates()
        return {
            "t_step_s": est[0] if est else None,
            "t_sync_s": est[1] if est else None,
            "n_steps": self.n_steps,
            "n_syncs": self.n_syncs,
            "n_blocks": self.n_blocks,
            "per_rung": {str(h): r for h, r in self.per_rung().items()},
        }
