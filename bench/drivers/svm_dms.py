"""DMS-SVM over a mesh of chips: the program ``dms(backend="shard_map")``
runs, ``repro.core.svm.dms_shard_map_program``, driven job after job.

Set-up makes each worker's rows on its own chip from the seed, builds and
compiles the program and runs one job. The window then runs whole jobs
back to back, each ending in ``block_until_ready`` on its weights, until
``--seconds`` have passed; the rate counts every training sample of every
worker. With ``--trace 1`` a few jobs in the window are profiled, and the
program's compiled text is read after them.

The check, after the window: every job's weights against the plain
reference (``bench.references.svm_ref.dms``) on the same rows, computed on
one chip, by relative L2 distance. The test accuracy of each against the
reference's, on held-out rows drawn from the same hyperplane, is printed
beside it.
"""
from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import counts, datagen, trace as tr
from bench.references import svm_ref


def _shape(ctx):
    cfg, t = ctx.config, ctx.cell["traffic_params"]
    k = ctx.cell["chips"]
    n_local = int(cfg["samples"] * cfg["split"]["train"]) // k
    return k, n_local, cfg["features"], t["epochs_per_job"], \
        t["block_per_worker"]


def _data(ctx, mesh):
    """Each worker's rows, made on its own chip from the seed."""
    cfg, t = ctx.config, ctx.cell["traffic_params"]
    k, n_local, d, _, _ = _shape(ctx)
    return datagen.svm_train(ctx.seed, k, n_local, d, cfg["density"],
                             cfg["label_noise"],
                             NamedSharding(mesh, P(t["axis"])))


def _program(ctx, mesh):
    from repro.core import svm
    cfg, t = ctx.config, ctx.cell["traffic_params"]
    _, _, _, epochs, block = _shape(ctx)
    return svm.dms_shard_map_program(
        mesh, t["axis"], epochs=epochs, block_size=block, c=cfg["c"],
        grad_impl=t["grad_impl"], overlap=t["overlap"],
        topology=t["topology"])


def reference(ctx, xs, ys, precision: str = "highest"):
    """The reference's weights on one chip, from the same rows."""
    _, _, _, epochs, block = _shape(ctx)
    one = ctx.devices[0]
    xs1, ys1 = jax.device_put((xs, ys), one)
    return svm_ref.dms(xs1, ys1, epochs=epochs, block_size=block,
                       c=ctx.config["c"], precision=precision)


def numbers(ctx, ws, w_ref) -> dict:
    """Per job: relative L2 distance of its weights from the reference's,
    and the gap between their test accuracies on held-out rows."""
    cfg = ctx.config
    one = ctx.devices[0]
    with jax.default_device(one):
        xt, yt = datagen.svm_test(ctx.seed,
                                  int(cfg["samples"] * cfg["split"]["test"]),
                                  cfg["features"], cfg["density"],
                                  cfg["label_noise"])
        acc_ref = float(svm_ref.accuracy(w_ref, xt, yt))
        ws = jnp.stack([jax.device_put(w, one) for w in ws])
        rel = np.asarray(jnp.linalg.norm(ws - w_ref, axis=1)
                         / jnp.linalg.norm(w_ref))
        accs = np.asarray(jax.vmap(lambda w: svm_ref.accuracy(w, xt, yt))(ws))
    return {"w_rel_l2": rel, "test_acc_gap": np.abs(accs - acc_ref),
            "acc_ref": acc_ref, "acc": accs}


def control_readings(ctx) -> dict:
    """The control (the reference at ``"high"``) and each fault planted in
    the program, read against the reference at the cell's own size."""
    from bench import faults
    mesh = Mesh(np.array(ctx.devices), (ctx.cell["traffic_params"]["axis"],))
    xs, ys = _data(ctx, mesh)
    w0 = jax.device_put(jnp.zeros((ctx.config["features"],), jnp.float32),
                        NamedSharding(mesh, P()))
    w_ref = reference(ctx, xs, ys)
    outs = {"program": _program(ctx, mesh)(w0, xs, ys),
            "control": reference(ctx, xs, ys, "high")}
    for f in faults.SVM:
        with faults.svm(f):
            outs[f] = _program(ctx, mesh)(w0, xs, ys)
    got = numbers(ctx, list(outs.values()), w_ref)
    return {name: {"w_rel_l2": float(got["w_rel_l2"][i]),
                   "test_acc_gap": float(got["test_acc_gap"][i])}
            for i, name in enumerate(outs)}


def run(ctx):
    from repro.runtime.ladder import CompileCounter
    from bench.run import Result

    t = ctx.cell["traffic_params"]
    k, n_local, d, epochs, block = _shape(ctx)
    samples_per_job = epochs * k * (n_local // block) * block
    job = counts.svm_job_counts(n_local, block, epochs, t["overlap"],
                                t["topology"])

    counter = CompileCounter().install()
    mesh = Mesh(np.array(ctx.devices), (t["axis"],))
    t_data = time.perf_counter()
    xs, ys = _data(ctx, mesh)
    jax.block_until_ready((xs, ys))
    t_data = time.perf_counter() - t_data
    w0 = jax.device_put(jnp.zeros((d,), jnp.float32),
                        NamedSharding(mesh, P()))
    fn = _program(ctx, mesh)
    t_warm = time.perf_counter()
    fn(w0, xs, ys).block_until_ready()        # compiles (or loads) + runs
    t_warm = time.perf_counter() - t_warm
    counter.mark()
    setup_s = time.perf_counter() - ctx.t0
    ctx.log(phase="setup", setup_s=setup_s, data_s=t_data,
            first_job_s=t_warm, rows_per_worker=n_local, workers=k)

    outs, trace_dir, traced_jobs = [], None, 0
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while True:
        if ctx.trace and len(outs) == t["trace_after_jobs"]:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
            with tr.span("traced"):
                for _ in range(t["traced_jobs"]):
                    with tr.span("job"):
                        w = fn(w0, xs, ys)
                        w.block_until_ready()
                    outs.append(w)
            jax.profiler.stop_trace()
            traced_jobs = t["traced_jobs"]
        else:
            with tr.span("job"):
                w = fn(w0, xs, ys)
                w.block_until_ready()
            outs.append(w)
        if time.perf_counter() >= deadline:
            break
    window_s = time.perf_counter() - t0
    compiles = counter.since_mark
    peaks = {str(dv.id): int((dv.memory_stats() or {})
                             .get("peak_bytes_in_use", 0))
             for dv in ctx.devices}
    jobs = len(outs)
    ctx.log(phase="window", jobs=jobs, window_s=window_s,
            compiles_in_window=compiles, peak_bytes_in_use=peaks)
    programs = None
    if trace_dir is not None:
        # the program as it ran: the same call, so the executable comes
        # from memory, as set-up compiled or loaded it
        programs = [fn.lower(w0, xs, ys).compile().as_text()]
        ctx.log(phase="programs", compiles=counter.since_mark - compiles)

    # -- check: the reference on one chip, after the window
    t_ref = time.perf_counter()
    w_ref = reference(ctx, xs, ys)
    del xs, ys
    got = numbers(ctx, outs, w_ref)
    lim = ctx.cell["check"]
    v = got["w_rel_l2"]
    # a limit not yet set from chip readings is null: no job passes
    bad = ~np.isfinite(v) | (lim["w_rel_l2"] is None or v > lim["w_rel_l2"])
    checks = [{"name": "w_rel_l2", "value": float(np.max(v)),
               "limit": lim["w_rel_l2"]}]
    # the test accuracy gap is printed, not compared: the program and the
    # control both read 0, so no limit lies between them (PERF.md)
    ctx.log(phase="check", seconds=time.perf_counter() - t_ref,
            acc_ref=got["acc_ref"], acc_min=float(got["acc"].min()),
            test_acc_gap=float(np.max(got["test_acc_gap"])),
            jobs_out_of_limit=int(bad.sum()))
    return Result(
        attempted=jobs, failed=int(bad.sum()),
        end_to_end={"svm_samples_per_s": jobs * samples_per_job / window_s,
                    "setup_s": setup_s},
        counts={"traced_samples": traced_jobs * samples_per_job,
                "traced_jobs": traced_jobs,
                "traced_blocks": traced_jobs * job["blocks"],
                "traced_syncs": traced_jobs * job["syncs"],
                "compiles_in_window": compiles},
        checks=checks, memory_peak_bytes=max(peaks.values()),
        trace_dir=trace_dir, programs=programs)
