"""Fault-tolerant step runner: the control plane a real cluster drives.

Components:

* :class:`StragglerWatchdog` — per-step deadline timer. On a real pod this
  marks the step (and host) as straggling so the coordinator can trigger
  preemption-aware checkpointing or task re-slicing; here it records the
  event and (optionally) raises, which exercises the same restart path.
* :class:`FaultInjector` — deterministic failure/straggle injection for
  tests (``inject_failure_at`` step raises ``SimulatedFault``).
* :class:`StepRunner` — drives ``step_fn`` with checkpoint/restart:
  on failure, restores the latest checkpoint (params/opt/data cursor) and
  replays. ``max_restarts`` bounds the retry loop. Because batches are
  deterministic in (seed, step), replay is bitwise-consistent with a run
  that never failed — asserted in tests.

The runner is deliberately synchronous/CPU-testable; on a real deployment
the same loop runs unmodified per-controller, with the watchdog fed from
device heartbeats instead of wall-clock.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List

import jax

from repro.config.base import FaultToleranceConfig
from repro.core.telemetry import span, step_span


class SimulatedFault(RuntimeError):
    pass


class StragglerWatchdog:
    def __init__(self, deadline_sec: float):
        self.deadline = deadline_sec
        self.events: List[Dict[str, Any]] = []

    def check(self, step: int, elapsed: float) -> bool:
        """Record and report whether the step straggled."""
        if self.deadline and elapsed > self.deadline:
            self.events.append({"step": step, "elapsed": elapsed})
            return True
        return False


class FaultInjector:
    def __init__(self, cfg: FaultToleranceConfig):
        self.cfg = cfg
        self._fired = False

    def before_step(self, step: int) -> None:
        if self.cfg.inject_straggle_sec and step == max(0, self.cfg.inject_failure_at - 1):
            time.sleep(self.cfg.inject_straggle_sec)
        if step == self.cfg.inject_failure_at and not self._fired:
            self._fired = True          # fail exactly once, then recover
            raise SimulatedFault(f"injected fault at step {step}")


class StepRunner:
    """Checkpoint/restart training driver.

    ``step_fn(state, batch) -> (state, metrics)`` must be pure (jitted).
    ``make_pipeline(start_step) -> iterator`` rebuilds the data pipeline at a
    cursor — the restore path uses it to resume data exactly where the
    checkpoint was taken.

    Each step runs inside a ``repro.step`` profiler span numbered by its
    step, which holds the spans ``data`` (``next(pipeline)``), ``dispatch``
    (the ``step_fn`` call), ``fetch`` (``block_until_ready`` on the
    metrics and their ``float()``), ``save`` (``ckpt.save``) and
    ``ladder`` (``ladder.on_block``); a restore runs in ``restore``.
    ``metrics_log[i]["elapsed"]`` is the host time from just before the
    dispatch to the metrics being ready on the device: it leaves out the
    feed, the ``float()`` of the metrics, the save and the ladder hook.
    ``steps``, ``saves`` and ``restarts`` count the steps run (a replayed
    step again), the checkpoints saved and the restores.
    """

    def __init__(self, step_fn: Callable, ckpt_manager, fault_cfg: FaultToleranceConfig,
                 ckpt_interval: int, make_pipeline: Callable[[int], Any],
                 fingerprint: str = "", ladder=None):
        self.step_fn = step_fn
        self.ckpt = ckpt_manager
        self.cfg = fault_cfg
        self.interval = max(1, ckpt_interval)
        self.make_pipeline = make_pipeline
        self.fingerprint = fingerprint
        # optional H-ladder runtime (repro.runtime.ladder.LadderRuntime):
        # when set, each step is one sync block executed by the ladder's
        # current pre-compiled rung; after the block the controller may
        # switch rungs, in which case the (flushed) state continues under
        # the new compiled callable and the data pipeline is re-blocked
        # at the new H from its current cursor — no recompilation.
        self.ladder = ladder
        self.watchdog = StragglerWatchdog(fault_cfg.step_deadline_sec)
        self.injector = FaultInjector(fault_cfg)
        self.steps = 0
        self.saves = 0
        self.restarts = 0
        self.metrics_log: List[Dict[str, Any]] = []

    def run(self, state, start_step: int, num_steps: int):
        step = start_step
        pipeline = self.make_pipeline(step)
        end = start_step + num_steps
        while step < end:
            try:
                state, step, pipeline = self._run_until(state, step, end, pipeline)
            except SimulatedFault:
                self.restarts += 1
                if self.restarts > self.cfg.max_restarts:
                    raise
                state, step, pipeline = self._restore(state)
        return state, step

    def _run_until(self, state, step: int, end: int, pipeline):
        while step < end:
            with step_span(step):
                try:
                    with span("data"):
                        batch = next(pipeline)
                except StopIteration:
                    break
                self.injector.before_step(step)
                step_fn = (self.ladder.step_fn if self.ladder is not None
                           else self.step_fn)
                t0 = time.perf_counter()
                with span("dispatch"):
                    state, metrics = step_fn(state, batch)
                with span("fetch"):
                    jax.block_until_ready(jax.tree.leaves(metrics))
                    elapsed = time.perf_counter() - t0
                    values = {k: float(v) for k, v in metrics.items()}
                straggled = self.watchdog.check(step, elapsed)
                self.metrics_log.append(
                    {"step": step, "elapsed": elapsed,
                     "straggled": straggled, **values})
                self.steps += 1
                step += 1
                if step % self.interval == 0:
                    extra = {"data": pipeline.state()}
                    if self.ladder is not None:
                        extra["ladder"] = self.ladder.checkpoint_state()
                    with span("save"):
                        self.ckpt.save(step, state, extra=extra,
                                       fingerprint=self.fingerprint)
                    self.saves += 1
                if self.ladder is not None:
                    with span("ladder"):
                        state, switched = self.ladder.on_block(state)
                    if switched:
                        # same microbatch stream, re-blocked at the new H
                        pipeline = self.make_pipeline(
                            pipeline.state()["step"])
        return state, step, pipeline

    def _restore(self, like_state):
        with span("restore"):
            self.ckpt.wait()
            latest = self.ckpt.latest_step()
            if latest is None:
                # no checkpoint yet — restart from scratch
                return like_state, 0, self.make_pipeline(0)
            state, extra = self.ckpt.restore(
                like_state, expected_fingerprint=self.fingerprint)
            cursor = int(extra.get("data", {}).get("step", latest))
            if self.ladder is not None:
                if "ladder" in extra:
                    self.ladder.restore(extra["ladder"])
                state = self.ladder.place(state)
            return state, latest, self.make_pipeline(cursor)
