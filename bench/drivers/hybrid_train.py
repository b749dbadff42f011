"""The interleaved Mamba-2/attention hybrid's trainer step (granite-4.0-h,
``repro.models.hybrid.InterleavedHybridModel``), built by
``repro.launch.train.build_trainer`` and driven by
``repro.runtime.ft.StepRunner``, as ``train()`` drives it.

It runs as ``bench/drivers/lm_train.py`` runs the dense decoder, and takes
that driver's pipeline span (``_Spanned``), window (``_window``) and the
check's numbers (``numbers``, ``fed_tokens_off``). What differs is its
own:

* the key map from the published config (``_model_config``), whose keys
  stand at the top level of the configuration's file, as the catalog
  gives them, and so the reference's batches (``reference_batches``,
  ``bench.datagen.lm_batch`` as the dense decoder's, ids drawn from the
  chip's slice of the vocabulary);
* the weights (``shapes``, ``params_fn``): the trainer's layout, float32,
  Mamba-2's own initialisation of A, Δ's bias and D
  (``assumed`` in the configuration's file);
* the reference (``bench.references.granite_ref``) and the faults planted
  in it: the float8 control and the SSD state left uncarried across
  chunks;
* memory: the weights and AdamW's moments fill most of the chip, so the
  3-step change is read against the initial weights made anew inside the
  reading (``change_from``), never beside the trainer's state, and the
  reference donates its weights.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, datagen, trace as tr
from bench.drivers import lm_train as LT
from bench.references import granite_ref

# published config key → the program's ModelConfig field; with no
# experts the MLP is the config's shared MLP
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "shared_intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings",
           "attention_multiplier": "attention_multiplier",
           "embedding_multiplier": "embedding_multiplier",
           "residual_multiplier": "residual_multiplier",
           "logits_scaling": "logits_scaling",
           "position_embedding_type": "position_embedding"}
# ... and of its SSMConfig
_SSM = {"mamba_d_state": "state_dim", "mamba_d_head": "head_dim",
        "mamba_expand": "expand", "mamba_chunk_size": "chunk_size",
        "mamba_d_conv": "conv_width"}
# what the program's Mamba-2 and attention layers compute, as the keys
# that say so must read
_FIXED = {"mamba_n_groups": 1, "mamba_conv_bias": True,
          "mamba_proj_bias": False, "attention_bias": False,
          "num_local_experts": 0, "hidden_act": "silu",
          "normalization_function": "rmsnorm"}


def _model_config(c: dict):
    """The program's ModelConfig from the configuration's file, whose
    top level holds the published config's keys."""
    from repro.config import get_arch
    off = {k: c.get(k) for k, v in _FIXED.items() if c.get(k) != v}
    if c["mamba_n_heads"] * c["mamba_d_head"] \
            != c["mamba_expand"] * c["hidden_size"]:
        off["mamba_n_heads"] = c["mamba_n_heads"]
    if off:
        raise ValueError(f"{c['name']}: the program has no layer for "
                         f"{off}")
    base = get_arch(c["arch"])
    return dataclasses.replace(
        base, **{f: c[k] for k, f in _FIELDS.items()},
        layer_types=tuple(c["layer_types"]),
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        ssm=dataclasses.replace(base.ssm,
                                **{f: c[k] for k, f in _SSM.items()}))


def shapes(c: dict) -> dict:
    """The weight layout: embedding (V, D); each kind of layer stacked on
    a leading axis in order, each layer two RMSNorm scales, its mixer and
    the SwiGLU w_gate/w_up (D, F), w_down (F, D); the final norm. Mamba-2
    mixer: in_x/in_z (D, E), in_b/in_c (D, N), in_dt (D, H), dt_bias,
    a_log, d_skip (H,), conv_x_w (W, E) and conv_b_w/conv_c_w (W, N) with
    their biases, gate_norm (E,), out (E, D), E = H·P. Attention: wq (D, H,
    hd), wk/wv (D, KV, hd), wo (H, hd, D)."""
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    mh, n, w = c["mamba_n_heads"], c["mamba_d_state"], c["mamba_d_conv"]
    e = mh * c["mamba_d_head"]
    mixers = {
        "mamba": lambda L: {
            "in_x": (L, d, e), "in_z": (L, d, e), "in_b": (L, d, n),
            "in_c": (L, d, n), "in_dt": (L, d, mh), "dt_bias": (L, mh),
            "a_log": (L, mh), "d_skip": (L, mh), "conv_x_w": (L, w, e),
            "conv_x_b": (L, e), "conv_b_w": (L, w, n), "conv_b_b": (L, n),
            "conv_c_w": (L, w, n), "conv_c_b": (L, n),
            "gate_norm": (L, e), "out": (L, e, d)},
        "attention": lambda L: {
            "wq": (L, d, h, hd), "wk": (L, d, kv, hd),
            "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
    }
    layers = {}
    for kind, mixer in mixers.items():
        n_kind = c["layer_types"].count(kind)
        if n_kind:
            layers[kind] = {
                "ln1": {"scale": (n_kind, d)}, "mixer": mixer(n_kind),
                "ln2": {"scale": (n_kind, d)},
                "mlp": {"w_gate": (n_kind, d, f), "w_up": (n_kind, d, f),
                        "w_down": (n_kind, f, d)}}
    return {"embed": {"embedding": (c["vocab_size"], d)},
            "layers": layers, "final_norm": {"scale": (d,)}}


def _init(path: str, shape, k):
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "gate_norm", "d_skip"):
        return jnp.ones(shape, jnp.float32)
    if leaf.startswith("conv_") and leaf.endswith("_b"):
        return jnp.zeros(shape, jnp.float32)
    if leaf == "a_log":                          # A = −U[1, 16]
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        # softplus⁻¹ of Δ log-uniform in [1e-3, 1e-1], Mamba-2's own
        lo, hi = np.log(1e-3), np.log(1e-1)
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))
    if path == "embed/embedding":
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    # a projection (or a conv's taps): normal over its fan-in
    fan_in = shape[1] * (shape[2] if leaf == "wo" else 1)
    return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)


def params_fn(c: dict):
    """``fn(key) -> params``: float32 weights from a key, jittable."""
    is_shape = lambda x: isinstance(x, tuple) and all(
        isinstance(e, int) for e in x)
    flat, tree = jax.tree.flatten_with_path(shapes(c), is_leaf=is_shape)
    names = ["/".join(p.key for p in path) for path, _ in flat]

    def fn(k):
        keys = jax.random.split(k, len(flat))
        return jax.tree.unflatten(
            tree, [_init(n, s, kk)
                   for n, (_, s), kk in zip(names, flat, keys)])
    return fn


def change_from(c: dict):
    """``fn(params, key)``: the leaf norms (``compare.leaf_norms``) of
    params less the weights ``params_fn`` makes from the key, which are
    made inside the reading, one leaf at a time as it is read."""
    make = params_fn(c)
    return jax.jit(lambda p, k: compare.change_norms(p, make(k)))


def _reference(c, opt, precision, seed, batches, carry=True):
    """Loss per step, first gradient's and the 3-step change's leaf norms
    of the reference trained from the benchmark's weights, laid out one
    dict a layer (``granite_ref.unstack``)."""
    k = datagen.key(seed, 1)
    kinds, make = c["layer_types"], params_fn(c)
    params = jax.jit(lambda k: granite_ref.unstack(make(k), kinds))(k)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = granite_ref.make_step(c, opt, precision, carry)
    losses, g1 = [], None
    for t, b in enumerate(batches, start=1):
        params, m, v, loss, gnorm = step(params, m, v, t,
                                         jnp.asarray(b["tokens"]),
                                         jnp.asarray(b["targets"]))
        losses.append(float(loss))
        if g1 is None:
            g1 = np.asarray(gnorm)
    del m, v
    stacked = jax.jit(lambda p: granite_ref.stack(p, kinds))(params)
    del params
    return np.array(losses), g1, np.asarray(change_from(c)(stacked, k))


def reference_batches(ctx) -> list:
    """The batches of the cell's first steps, by the benchmark's own copy
    of the generator."""
    t = ctx.cell["traffic_params"]
    return [datagen.lm_batch(ctx.seed, i, t["global_batch"], t["seq_len"],
                             ctx.config["vocab_size"])
            for i in range(t["check_steps"])]


def reference_readings(ctx, precision: str = "highest", carry: bool = True):
    """The reference's readings for the cell's first steps."""
    c, t = ctx.config, ctx.cell["traffic_params"]
    return _reference(c, t["optimizer"], precision, ctx.seed,
                      reference_batches(ctx), carry)


# the faults planted in the reference, put in the program's place
REFERENCE_FAULTS = ("state_unchanged", "answer_altered",
                    "ssd_state_not_carried")


def control_readings(ctx) -> dict:
    """The control (the reference in float8) and the faults planted in
    the reference, each read against the reference at the cell's own
    size. One row a step: no half batch to leave out."""
    ref = reference_readings(ctx)
    loss, g1, change = ref
    keys = ("loss_gap", "grad_norm_gap", "update_norm_gap")
    doubled = change.copy()
    doubled[0] *= 2                       # the embedding moved double
    out = {"control": reference_readings(ctx, "fp8"),
           "state_unchanged": (loss, g1, np.zeros_like(change)),
           "answer_altered": (loss, g1, doubled),
           "ssd_state_not_carried": reference_readings(ctx, carry=False)}
    return {name: {k: LT.numbers(r, ref)[k] for k in keys}
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
    """Put the benchmark's weights in place of the trainer's own."""
    c = ctx.config
    got = jax.tree.map(lambda x: tuple(x.shape), state["params"])
    if got != shapes(c):
        raise ValueError(f"trainer weight layout {got} is not the "
                         f"benchmark's {shapes(c)}")
    make = jax.jit(params_fn(c), out_shardings=jax.tree.map(
        lambda x: x.sharding, state["params"]))
    state = {**state, "params": None}            # frees the trainer's own
    state["params"] = make(datagen.key(ctx.seed, 1))
    return state


def run(ctx):
    from repro.checkpoint import CheckpointManager
    from repro.config import config_fingerprint
    from repro.core.telemetry import ATTENTION, SSD
    from repro.runtime import StepRunner
    from repro.runtime.ladder import CompileCounter
    from bench.run import Result

    c, t = ctx.config, ctx.cell["traffic_params"]
    b, s, n_check = t["global_batch"], t["seq_len"], t["check_steps"]
    counter = CompileCounter().install()
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    attn0, ssd0 = ATTENTION.counts(), SSD.counts()
    step, state, make_pipeline, cfg, mesh = _trainer(ctx, ckpt_dir)
    state = _own_weights(ctx, state)

    fed = {}

    def step_fn(st, batch):
        fed["batch"] = batch
        with tr.span("step_fn"):
            return step(st, batch)

    kept = {}
    runner = StepRunner(
        step_fn, CheckpointManager(cfg.checkpoint), cfg.fault,
        cfg.checkpoint.interval_steps,
        lambda start: LT._Spanned(make_pipeline(start), n_check, kept),
        fingerprint=config_fingerprint(cfg))

    with jax.set_mesh(mesh):              # as train() runs its steps
        # the first steps: warm-up, and what the check compares
        t_first = time.perf_counter()
        state, n = runner.run(state, 0, 1)
        t_first = time.perf_counter() - t_first
        g1 = np.asarray(compare.leaf_norms(state["opt"]["mu"])) \
            / (1.0 - t["optimizer"]["beta1"])
        state, n = runner.run(state, n, n_check - 1)
        change = np.asarray(change_from(c)(state["params"],
                                           datagen.key(ctx.seed, 1)))
        losses = np.array([m["loss"]
                           for m in runner.metrics_log[:n_check]])
        counter.mark()
        setup_s = time.perf_counter() - ctx.t0
        ctx.log(phase="setup", setup_s=setup_s, first_step_s=t_first,
                losses=losses.tolist(), attention_calls=ATTENTION.since(attn0),
                ssd_calls=SSD.since(ssd0))
        state, done, window_s, trace_dir, traced = LT._window(ctx, runner,
                                                              state, n)
        compiles = counter.since_mark
        programs = None
        if trace_dir is not None:
            # the step as it ran, its executable served from memory
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
    del state, runner, step, step_fn, fed
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(ctx)
    got = LT.numbers((losses, g1, change), ref)
    got["fed_tokens_off"] = LT.fed_tokens_off(kept,
                                              reference_batches(ctx))
    lim = ctx.cell["check"]
    # the loss is printed, not compared, as in the dense decoder's cell
    checks = [{"name": k, "value": got[k], "limit": lim[k]}
              for k in ("grad_norm_gap", "update_norm_gap",
                        "fed_tokens_off")]
    names = compare.leaf_names(shapes(c))
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
