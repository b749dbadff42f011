"""End-to-end training driver.

Wires every subsystem: arch config → mesh → sharding rules → model → MSF
sync engine → optimizer → data pipeline → checkpoint manager →
fault-tolerant step runner. The mesh spans the devices the process has
(``data`` = device count, ``model`` = 1): the CPU smoke path
(``--arch smollm-360m --smoke``) and a full-width run on one chip or a
4-chip host use the same code. Local SGD over the chips is
``--set mesh.replica_axis=data --set sync.strategy=periodic``.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --set steps=20 --set sync.strategy=periodic --set sync.period=4
"""
from __future__ import annotations

import functools
import json
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import TrainConfig, config_fingerprint, get_arch, get_smoke
from repro.config.cli import apply_overrides, build_parser
from repro.core import local_sgd as LS
from repro.core import sync as SY
from repro.core.telemetry import ATTENTION
from repro.data.pipeline import DataPipeline
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_test_mesh, test_mesh_config
from repro.models.registry import build_model
from repro.runtime import StepRunner
from repro.sharding import rules_for


class _Blocked:
    """Groups H microbatches into one (H, B, …) train block.

    Assembly is host-side numpy (``DataPipeline.next_host``): the H-ladder
    path feeds the stacked block straight into a pre-compiled executable,
    and any eager jnp op here would compile on first use and break the
    ladder's zero-recompile-after-warmup guarantee.
    """

    def __init__(self, inner, h: int):
        self.inner = inner
        self.h = h

    def state(self):
        return self.inner.state()

    def __iter__(self):
        return self

    def __next__(self):
        mbs = [self.inner.next_host() for _ in range(self.h)]
        return {k: np.stack([m[k] for m in mbs]) for k in mbs[0]}


def _build_ladder(cfg: TrainConfig, mesh, jitted, state, shardings,
                  telemetry, counter, replicas: int):
    """Ladder warmup: AOT-compile every rung + the switch transform, then
    hand them to a :class:`repro.runtime.ladder.LadderRuntime` with the
    controller in ladder mode. ``counter.mark()`` closes the warmup
    window the zero-recompile assertion measures from."""
    from repro.core.autotune import DCN_BW, AdaptiveController
    from repro.runtime.ladder import (LadderRuntime, _avals, compile_rungs)

    rungs = cfg.sync.ladder_rungs()
    sample = DataPipeline(cfg.data, cfg.model).next_host()
    with jax.set_mesh(mesh):
        compiled = compile_rungs(jitted, state, sample, rungs)
        switch = jax.jit(
            lambda s: LS.ladder_switch_state(s, cfg),
            in_shardings=(shardings,), out_shardings=shardings,
            donate_argnums=(0,)).lower(_avals(state)).compile()
    timed = {hh: LS.timed_step(fn, hh, telemetry, jit_step=False)
             for hh, fn in compiled.items()}
    ctrl = AdaptiveController(
        cfg.sync,
        param_bytes_per_chip=max(1, 4 * cfg.model.param_count()
                                 // max(1, mesh.devices.size)),
        replicas=max(2, replicas), link_bw=DCN_BW,
        lr=cfg.optimizer.learning_rate, telemetry=telemetry,
        ladder=rungs)
    if counter is not None:
        counter.mark()
    return LadderRuntime(timed, switch, ctrl, telemetry=telemetry,
                         shardings=shardings, compile_counter=counter)


def build_trainer(cfg: TrainConfig, mesh):
    """Returns (step_fn, initial state, make_pipeline, model, telemetry,
    ladder).

    With ``sync.adaptive`` on a replica-sync strategy the trainer builds
    the **H-ladder runtime**: the train block is AOT-compiled for every
    rung of ``cfg.sync.ladder_rungs()`` (shared state layout — one traced
    signature, one executable per batch shape), the switch transform is
    AOT-compiled too, and ``ladder`` is a live
    :class:`repro.runtime.ladder.LadderRuntime` the step runner drives —
    the controller moves H *mid-run* with zero XLA compiles after the
    ladder warmup (counted by the ladder's ``CompileCounter``). In that
    mode ``step_fn`` is the un-warmed jit and must not be called directly
    (use ``ladder.step_fn``). With ``sync.adaptive`` on ``sync_every_step``
    the step is only wrapped in the block-time telemetry hook and the
    driver reports a recommendation for the next launch; ``telemetry`` is
    a live :class:`repro.core.telemetry.BlockTelemetry` in both adaptive
    modes, ``None`` otherwise.
    """
    rules = rules_for(cfg.mesh, mesh)
    model = build_model(cfg.model, scan_layers=cfg.scan_layers,
                        remat=cfg.remat)
    use_replicas = SY.needs_replica_axis(cfg.sync)
    replicas = cfg.mesh.axis_size(cfg.mesh.replica_axis or "pod") \
        if use_replicas else 0

    build_ladder = cfg.sync.adaptive and use_replicas
    counter = None
    if build_ladder:
        # install before any compilation so warmup compiles are counted
        # (and everything after mark() must be zero)
        from repro.runtime.ladder import CompileCounter
        counter = CompileCounter().install()

    with jax.set_mesh(mesh):
        # built in place: each replica's copy is made on its own devices
        # (K full replicas would not fit on the first one)
        init = functools.partial(LS.init_state, model, cfg,
                                 replicas=replicas)
        key = jax.random.key(cfg.seed)
        axes = LS.build_state_axes(model, cfg, replicated=use_replicas)
        shardings = LS.state_shardings(
            axes, rules, jax.tree.map(lambda x: x.shape,
                                      jax.eval_shape(init, key)))
        state = jax.jit(init, out_shardings=shardings)(key)
        step = LS.make_train_step(model, cfg, mesh, rules)
        jitted = jax.jit(step, in_shardings=(shardings, None),
                         out_shardings=(shardings, None),
                         donate_argnums=(0,))

    h = cfg.sync.period if use_replicas else 0

    telemetry = None
    ladder = None
    if cfg.sync.adaptive:
        from repro.core.telemetry import BlockTelemetry
        telemetry = BlockTelemetry()
        if build_ladder:
            ladder = _build_ladder(cfg, mesh, jitted, state, shardings,
                                   telemetry, counter, replicas)
        else:
            # wrap the already-sharded/donating jit — jit_step=False
            # keeps it
            jitted = LS.timed_step(jitted, 1, telemetry, jit_step=False)

    def make_pipeline(start_step: int):
        pipe = DataPipeline(cfg.data, cfg.model, start_step=start_step)
        cur_h = ladder.h if ladder is not None else h
        if not cur_h:
            return pipe
        return _Blocked(pipe, cur_h)

    return jitted, state, make_pipeline, model, telemetry, ladder


def train(argv=None):
    """Parse ``argv`` as the CLI does, train, and flush the replicas.

    Returns ``(summary, state)``: the JSON summary ``main`` prints and the
    final state after :func:`repro.core.local_sgd.finalize_state`.
    """
    p = build_parser("end-to-end trainer")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config on local devices")
    p.add_argument("--steps", type=int, default=20)
    args = p.parse_args(argv)
    use_compile_cache()

    model_cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    n_dev = len(jax.devices())
    mesh = make_test_mesh((n_dev, 1))
    mesh_cfg = test_mesh_config((n_dev, 1))

    from repro.config.base import DataConfig
    cfg = TrainConfig(model=model_cfg, mesh=mesh_cfg,
                      data=DataConfig(seq_len=64 if args.smoke else 4096,
                                      global_batch=mesh_cfg.axis_size(
                                          mesh_cfg.data_axis) * 2),
                      steps=args.steps,
                      # full width keeps one layer's activations live
                      remat="none" if args.smoke else "full")
    cfg = apply_overrides(cfg, args.overrides)

    traced_before = ATTENTION.counts()
    step, state, make_pipeline, _, telemetry, ladder = build_trainer(cfg,
                                                                     mesh)
    ckpt = CheckpointManager(cfg.checkpoint)
    runner = StepRunner(step, ckpt, cfg.fault, cfg.checkpoint.interval_steps,
                        make_pipeline, fingerprint=config_fingerprint(cfg),
                        ladder=ladder)

    t0 = time.time()
    with jax.set_mesh(mesh):
        state, final_step = runner.run(state, 0, cfg.steps)
    dt = time.time() - t0
    losses = [m["loss"] for m in runner.metrics_log]
    step_s = [m["elapsed"] for m in runner.metrics_log]
    out = {
        "arch": model_cfg.name,
        "steps": final_step,
        "wall_s": round(dt, 2),
        # the first step includes compilation
        "first_step_s": round(step_s[0], 4) if step_s else None,
        "rest_step_s": (round(sum(step_s[1:]) / len(step_s[1:]), 4)
                        if len(step_s) > 1 else None),
        "first_loss": round(losses[0], 4) if losses else None,
        "last_loss": round(losses[-1], 4) if losses else None,
        "steps_run": runner.steps,
        "saves": runner.saves,
        "restarts": runner.restarts,
        "stragglers": len(runner.watchdog.events),
        # attention layers traced in this run's programs, by path
        "attention_calls": ATTENTION.since(traced_before),
    }
    if ladder is not None:
        # the live H-ladder run: trajectory, switches, per-rung telemetry
        # and the compile count the adaptive-smoke CI job asserts on
        out["adaptive"] = ladder.to_dict()
        out["adaptive"]["controller_history"] = [
            list(t) for t in ladder.controller.history]
    elif telemetry is not None:
        out["adaptive"] = adaptive_report(cfg, mesh, telemetry)
    with jax.set_mesh(mesh):
        state = LS.finalize_state(state, cfg)
    return out, state


def main(argv=None) -> None:
    out, _ = train(argv)
    print(json.dumps(out))


def adaptive_report(cfg: TrainConfig, mesh, telemetry) -> dict:
    """The non-ladder adaptive summary: the re-solve's recommendation for
    the NEXT launch (``sync_every_step`` has no block to ladder). A
    single-H run can't split T_step/T_sync from block times alone; fall
    back to measured step + analytic sync in that case. The replica count
    uses the same ``or "pod"`` fallback as ``build_trainer`` — an unset
    ``replica_axis`` must not change which axis the report prices."""
    from repro.core.autotune import DCN_BW, TuneInputs, choose_period
    est = telemetry.estimates()
    t_step = est[0] if est else telemetry.per_step_s()
    rec = None
    if t_step:
        inp = TuneInputs(
            param_bytes_per_chip=max(1, 4 * cfg.model.param_count()
                                     // max(1, mesh.devices.size)),
            replicas=max(2, cfg.mesh.axis_size(
                cfg.mesh.replica_axis or "pod")),
            step_time_s=t_step, link_bw=DCN_BW,
            lr=cfg.optimizer.learning_rate)
        rec = choose_period(
            inp, cfg.sync,
            target_overhead=cfg.sync.adapt_target_overhead,
            max_drift=cfg.sync.adapt_max_drift,
            sync_time_override=est[1] if est else None)
    return {"telemetry": telemetry.to_dict(), "recommended_h": rec}


if __name__ == "__main__":
    main()
