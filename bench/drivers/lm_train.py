"""The LM trainer's step, built by ``repro.launch.train.build_trainer``
and driven by ``repro.runtime.ft.StepRunner``, as ``train()`` drives it.

Set-up builds the trainer from the configuration's published sizes,
replaces its initial weights with the benchmark's own (made on the chip
from the seed, in the layout the trainer keeps), and runs the first three
steps through the runner with the trainer's own data pipeline
(``make_pipeline``): the first compiles. Those steps are also what the
check compares: each step's loss, the first gradient as AdamW's first
moment holds it after step 1 (μ₁ = (1−β₁)·g), and each weight's change
after step 3, read before step 4 runs.

The window then runs one step after another, each through
``StepRunner.run`` (the runner blocks on every step and fetches its
metrics), until ``--seconds`` have passed. Each batch the pipeline hands
out and each step carry host spans (``bench.data``, ``bench.step_fn``)
for the trace. With ``--trace 1`` a few steps are profiled, and the
step's compiled text is read after the window.

After the window the trainer's state is freed and the plain reference
(``bench.references.lm_ref``) trains the same weights on the same three
batches in float32, drawn by the benchmark's own copy of the generator
(``bench.datagen.lm_batch``); the tokens the pipeline fed those steps
are compared with that copy's exactly.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, datagen, trace as tr
from bench.references import lm_ref

# published config key → the program's ModelConfig field
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "head_dim": "head_dim", "rope_theta": "rope_theta",
           "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings"}


def _model_config(cfg: dict):
    import dataclasses
    from repro.config import get_arch
    c = cfg["config"]
    return dataclasses.replace(get_arch(cfg["arch"]),
                               **{f: c[k] for k, f in _FIELDS.items()})


class _Spanned:
    """The trainer's pipeline, each batch drawn under the ``data`` span;
    the host copies of the batches of steps below ``keep`` go to
    ``kept``, by step, for the check."""

    def __init__(self, pipe, keep: int, kept: dict):
        self.pipe, self.keep, self.kept = pipe, keep, kept

    def state(self):
        return self.pipe.state()

    def __iter__(self):
        return self

    def __next__(self):
        step = self.pipe.state()["step"]
        with tr.span("data"):
            batch = next(self.pipe)
        if step < self.keep:
            self.kept[step] = {k: np.asarray(v) for k, v in batch.items()}
        return batch


def fed_tokens_off(kept: dict, batches: list) -> int:
    """How many token and target ids the pipeline fed the check's steps
    differ from the reference's batches (a step not fed counts whole)."""
    off = 0
    for i, b in enumerate(batches):
        for k, v in b.items():
            got = kept.get(i, {}).get(k)
            off += int(v.size if got is None or got.shape != v.shape
                       else np.count_nonzero(got != v))
    return off


def _reference(c, opt, precision, seed, batches):
    """Loss per step, first gradient's and the 3-step change's leaf norms
    of the reference trained from the benchmark's weights."""
    p0 = jax.jit(datagen.lm_params_fn(c))(datagen.key(seed, 1))
    params = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    step = lm_ref.make_step(c, opt, precision)
    losses, g1 = [], None
    for t, b in enumerate(batches, start=1):
        params, m, v, loss, gnorm = step(params, m, v, t,
                                         jnp.asarray(b["tokens"]),
                                         jnp.asarray(b["targets"]))
        losses.append(float(loss))
        if g1 is None:
            g1 = np.asarray(gnorm)
    del m, v
    change = np.asarray(compare.change_norms(params, p0))
    return np.array(losses), g1, change


def numbers(prog, ref) -> dict:
    """The compared numbers of a (losses, first gradient's leaf norms,
    3-step change's leaf norms) triple against the reference's. Leaves
    whose first reference gradient is nought to rounding are left out of
    the change (``compare.moved``)."""
    loss, g1, change = prog
    r_loss, r_g1, r_change = ref
    grad = compare.norm_gap(g1, r_g1)
    upd = compare.norm_gap(change, r_change, compare.moved(r_g1))
    gaps = np.abs(np.asarray(loss) - r_loss) / np.abs(r_loss)
    # the first step's loss: the later steps' follow AdamW's first update,
    # which is about sign(g) per weight, so rounding of tiny gradients
    # decides them and their gap swings from seed to seed
    return {"loss_gap": float(gaps[0]), "loss_gaps": gaps.tolist(),
            "grad_norm_gap": grad["value"], "grad_worst": grad["leaf"],
            "update_norm_gap": upd["value"], "update_worst": upd["leaf"]}


def reference_batches(ctx) -> list:
    """The batches of the cell's first steps, by the benchmark's own copy
    of the generator."""
    c, t = ctx.config["config"], ctx.cell["traffic_params"]
    return [datagen.lm_batch(ctx.seed, i, t["global_batch"], t["seq_len"],
                             c["vocab_size"])
            for i in range(t["check_steps"])]


def reference_readings(ctx, precision: str = "highest", rows=None):
    """The reference's readings for the cell's first steps; ``rows`` keeps
    only those rows of each batch (a fault planted in the reference)."""
    c, t = ctx.config["config"], ctx.cell["traffic_params"]
    batches = reference_batches(ctx)
    if rows is not None:
        batches = [{k: v[rows] for k, v in b.items()} for b in batches]
    return _reference(c, t["optimizer"], precision, ctx.seed, batches)


def control_readings(ctx) -> dict:
    """The control (the reference in float8) and the faults planted in
    the reference put in the program's place, each read against the
    reference at the cell's own size."""
    ref = reference_readings(ctx)
    half = slice(0, ctx.cell["traffic_params"]["global_batch"] // 2)
    loss, g1, change = ref
    keys = ("loss_gap", "grad_norm_gap", "update_norm_gap")
    doubled = change.copy()
    doubled[0] *= 2                       # the embedding moved double
    out = {"control": reference_readings(ctx, "fp8"),
           "half_batch": reference_readings(ctx, rows=half),
           "state_unchanged": (loss, g1, np.zeros_like(change)),
           "answer_altered": (loss, g1, doubled)}
    return {name: {k: numbers(r, ref)[k] for k in keys}
            for name, r in out.items()}


def _trainer(ctx, ckpt_dir):
    """The trainer as ``train()`` builds it, from the configuration's
    published sizes and the cell's traffic; returns (step, state,
    make_pipeline, cfg, mesh)."""
    from repro.config import TrainConfig
    from repro.config.base import DataConfig
    from repro.config.cli import apply_overrides
    from repro.launch.mesh import test_mesh_config
    from repro.launch.train import build_trainer

    t = ctx.cell["traffic_params"]
    chips = ctx.cell["chips"]
    mesh = jax.make_mesh((chips, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=ctx.devices)
    cfg = TrainConfig(
        model=_model_config(ctx.config), mesh=test_mesh_config((chips, 1)),
        data=DataConfig(seq_len=t["seq_len"], global_batch=t["global_batch"],
                        seed=ctx.seed),
        steps=t["check_steps"], remat=t["remat"],
        seed=ctx.seed & 0x7FFFFFFF)
    cfg = apply_overrides(cfg, [
        f"optimizer.{k}={v}" for k, v in t["optimizer"].items()] + [
        f"checkpoint.directory={ckpt_dir}",
        # no save inside the window: this mix measures steps alone
        "checkpoint.interval_steps=1000000000"])
    step, state, make_pipeline, _, _, _ = build_trainer(cfg, mesh)
    return step, state, make_pipeline, cfg, mesh


def _own_weights(ctx, state):
    """Put the benchmark's weights in place of the trainer's own; returns
    the state and the jitted maker (to read the change against)."""
    c = ctx.config["config"]
    shapes = jax.tree.map(lambda x: tuple(x.shape), state["params"])
    want = datagen.lm_shapes(c)
    if shapes != want:
        raise ValueError(f"trainer weight layout {shapes} is not the "
                         f"benchmark's {want}")
    make = jax.jit(datagen.lm_params_fn(c), out_shardings=jax.tree.map(
        lambda x: x.sharding, state["params"]))
    state = {**state, "params": None}            # frees the trainer's own
    state["params"] = make(datagen.key(ctx.seed, 1))
    return state, make


def _window(ctx, runner, state, n):
    """Steps back to back until ``--seconds`` have passed (a few of them
    traced with ``--trace 1``)."""
    t = ctx.cell["traffic_params"]
    trace_dir, traced, done = None, 0, 0
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while True:
        if ctx.trace and done == t["trace_after_steps"]:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
            with tr.span("traced"):
                for _ in range(t["traced_steps"]):
                    with tr.span("step"):
                        state, n = runner.run(state, n, 1)
            jax.profiler.stop_trace()
            traced = t["traced_steps"]
            done += traced
        else:
            with tr.span("step"):
                state, n = runner.run(state, n, 1)
            done += 1
        if time.perf_counter() >= deadline:
            break
    return state, done, time.perf_counter() - t0, trace_dir, traced


def run(ctx):
    from repro.checkpoint import CheckpointManager
    from repro.config import config_fingerprint
    from repro.runtime import StepRunner
    from repro.runtime.ladder import CompileCounter
    from bench.run import Result

    c, t = ctx.config["config"], ctx.cell["traffic_params"]
    b, s, n_check = t["global_batch"], t["seq_len"], t["check_steps"]
    counter = CompileCounter().install()
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    step, state, make_pipeline, cfg, mesh = _trainer(ctx, ckpt_dir)
    state, make_params = _own_weights(ctx, state)

    fed = {}

    def step_fn(st, batch):
        fed["batch"] = batch
        with tr.span("step_fn"):
            return step(st, batch)

    kept = {}
    runner = StepRunner(
        step_fn, CheckpointManager(cfg.checkpoint), cfg.fault,
        cfg.checkpoint.interval_steps,
        lambda start: _Spanned(make_pipeline(start), n_check, kept),
        fingerprint=config_fingerprint(cfg))

    with jax.set_mesh(mesh):              # as train() runs its steps
        # the first steps: warm-up, and what the check compares
        t_first = time.perf_counter()
        state, n = runner.run(state, 0, 1)
        t_first = time.perf_counter() - t_first
        g1 = np.asarray(compare.leaf_norms(state["opt"]["mu"])) \
            / (1.0 - t["optimizer"]["beta1"])
        state, n = runner.run(state, n, n_check - 1)
        change = np.asarray(compare.change_norms(
            state["params"], make_params(datagen.key(ctx.seed, 1))))
        losses = np.array([m["loss"]
                           for m in runner.metrics_log[:n_check]])
        counter.mark()
        setup_s = time.perf_counter() - ctx.t0
        ctx.log(phase="setup", setup_s=setup_s, first_step_s=t_first,
                losses=losses.tolist())
        state, done, window_s, trace_dir, traced = _window(ctx, runner,
                                                           state, n)
        compiles = counter.since_mark
        programs = None
        if trace_dir is not None:
            # the step as it ran: the same jit, mesh and kinds of argument,
            # so its executable comes from memory, as set-up compiled or
            # loaded it
            programs = [step.lower(state, fed["batch"]).compile().as_text()]
            ctx.log(phase="programs", compiles=counter.since_mark - compiles)
    peaks = {str(d.id): int((d.memory_stats() or {})
                            .get("peak_bytes_in_use", 0))
             for d in ctx.devices}
    window_losses = [m["loss"] for m in runner.metrics_log[n_check:]]
    failed = int(sum(not np.isfinite(x) for x in window_losses))
    ctx.log(phase="window", steps=done, window_s=window_s,
            compiles_in_window=compiles, peak_bytes_in_use=peaks,
            last_loss=window_losses[-1],
            step_s=[m["elapsed"] for m in runner.metrics_log[n_check:]])
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    # -- check: free the trainer, then the reference on the same batches
    del state, runner, step, step_fn, make_params, fed
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(ctx)
    got = numbers((losses, g1, change), ref)
    got["fed_tokens_off"] = fed_tokens_off(kept, reference_batches(ctx))
    lim = ctx.cell["check"]
    # the loss is printed, not compared: no control or fault reading
    # stands far enough above the program's (PERF.md)
    checks = [{"name": k, "value": got[k], "limit": lim[k]}
              for k in ("grad_norm_gap", "update_norm_gap",
                        "fed_tokens_off")]
    names = compare.leaf_names(datagen.lm_shapes(c))
    ctx.log(phase="check", seconds=time.perf_counter() - t_ref,
            loss=losses.tolist(), loss_ref=ref[0].tolist(),
            loss_gaps=got["loss_gaps"], loss_gap=got["loss_gap"],
            grad_worst_leaf=names[got["grad_worst"]],
            update_worst_leaf=names[got["update_worst"]],
            leaves_left_out=[names[i] for i in
                             np.flatnonzero(~compare.moved(ref[1]))])
    return Result(
        attempted=done, failed=failed,
        end_to_end={"lm_tokens_per_s": done * b * s / window_s,
                    "setup_s": setup_s},
        counts={"traced_steps": traced, "traced_tokens": traced * b * s,
                "compiles_in_window": compiles},
        checks=checks, memory_peak_bytes=max(peaks.values()),
        trace_dir=trace_dir, programs=programs)
