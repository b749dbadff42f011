"""Paper-figure reproductions (one function per table/figure).

All figures run on synthetic stand-ins matched to the paper datasets'
(n, d, sparsity) — see repro.data.synthetic — scaled down where noted so
the whole suite finishes in minutes on CPU. Output: CSV rows on stdout +
JSON records under experiments/paper/.

  fig1_3   — CV accuracy vs block size (sequential SRDMS)      [Figs 1, 3]
  fig2_4   — training time vs block size (sequential)          [Figs 2, 4]
  fig5_9   — parallel vs sequential convergence (DMS≡SRDMS)    [Figs 5–9]
  fig10_15 — comm/compute time breakdown vs MSF × parallelism  [Figs 10–15]
  table2   — sequential vs parallel timing + accuracy          [Table II]

Beyond-paper perf sections:

  overlap_sweep — blocking vs delayed vs chunked sync step time across the
                  H ladder (the overlap-aware sync engine's claim)
  gossip_sweep  — ring/pairwise gossip vs global all-reduce: O(1) neighbor
                  wire bytes vs 2P(K−1)/K, accuracy parity at the
                  autotuned (spectral-gap-capped) H, measured sync time
  hinge_kernel  — fused Pallas hinge block-gradient vs the jnp reference
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import record
from repro.core import svm
from repro.data import make_svm_dataset

OUT_DIR = record.OUT_DIR

# scaled-down sample counts (feature dims stay faithful — they set the
# communication volume, which is what the paper measures)
BENCH_N = {"ijcnn1": 8_000, "webspam": 12_000, "epsilon": 4_000}
EPOCHS = 12
_CPU_WORKERS = 8


def _ds(name):
    return make_svm_dataset(name, seed=0, n_override=BENCH_N[name])


def _save(name: str, rows: List[Dict]) -> None:
    record.save(name, rows)


def _workers() -> int:
    """K for the multi-worker sections: every chip present on an
    accelerator, 8 (fake) devices on a CPU host."""
    if jax.default_backend() == "cpu":
        return _CPU_WORKERS
    return len(jax.devices())


def _cpu_child(section: str, prefix: str) -> Optional[List[str]]:
    """On a CPU host with fewer than 8 devices, run ``section`` in a child
    that fakes 8 CPU devices and return its ``prefix`` lines; ``None``
    means run in this process. Only the CPU is ever faked: on an
    accelerator the section runs here, over the chips present."""
    if (jax.default_backend() != "cpu"
            or len(jax.devices()) >= _CPU_WORKERS):
        return None
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_CPU_WORKERS}"
    env["JAX_PLATFORMS"] = "cpu"   # the flag only fakes CPU devices
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.paper_figs", section],
        env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        return [f"{prefix},ERROR,,{out.stderr[-200:]}"]
    return [l for l in out.stdout.splitlines() if l.startswith(prefix)]


def fig1_3() -> List[str]:
    """CV accuracy vs block size, sequential SRDMS (paper Figs 1 & 3)."""
    lines = []
    rows = []
    for dataset in ("ijcnn1", "webspam"):
        ds = _ds(dataset)
        x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
        xcv, ycv = jnp.asarray(ds.x_cv), jnp.asarray(ds.y_cv)
        w0 = jnp.zeros(ds.features)
        for bs in (1, 2, 4, 8, 512, 1024):
            w = svm.srdms(w0, x, y, epochs=EPOCHS, block_size=bs)
            acc = float(svm.accuracy(w, xcv, ycv))
            obj = float(svm.hinge_objective(w, x, y))
            rows.append({"dataset": dataset, "block": bs, "cv_acc": acc,
                         "objective": obj})
            lines.append(f"fig1_3,{dataset},block={bs},{acc:.4f}")
    _save("fig1_3_accuracy_vs_block", rows)
    return lines


def fig2_4() -> List[str]:
    """Training time vs block size, sequential (paper Figs 2 & 4)."""
    lines = []
    rows = []
    for dataset in ("ijcnn1", "webspam"):
        ds = _ds(dataset)
        x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
        xcv, ycv = jnp.asarray(ds.x_cv), jnp.asarray(ds.y_cv)
        w0 = jnp.zeros(ds.features)
        for bs in (1, 2, 4, 8, 512, 1024):
            # paper methodology (§V-C2): the CV-accuracy + objective
            # convergence check runs at EVERY model synchronization, so
            # high MSF (small blocks) pays it thousands of times per
            # epoch — the overhead whose dilution Figs 2/4 plot
            t0 = time.perf_counter()
            w, hist = svm.srdms(w0, x, y, epochs=EPOCHS, block_size=bs,
                                x_cv=xcv, y_cv=ycv, eval_every_sync=True)
            jax.block_until_ready(w)
            dt = time.perf_counter() - t0
            rows.append({"dataset": dataset, "block": bs, "train_s": dt})
            lines.append(f"fig2_4,{dataset},block={bs},{dt*1e6:.0f}")
    _save("fig2_4_time_vs_block", rows)
    return lines


def fig5_9() -> List[str]:
    """Parallel (DMS) vs sequential-replica convergence (Figs 5–9)."""
    lines = []
    rows = []
    for dataset in ("ijcnn1", "webspam"):
        ds = _ds(dataset)
        xcv, ycv = jnp.asarray(ds.x_cv), jnp.asarray(ds.y_cv)
        w0 = jnp.zeros(ds.features)
        for workers in (2, 8, 32):
            for bs in (1, 8, 512):
                w = svm.dms(w0, ds.x_train, ds.y_train, workers=workers,
                            epochs=EPOCHS, block_size=bs)
                acc = float(svm.accuracy(w, xcv, ycv))
                rows.append({"dataset": dataset, "workers": workers,
                             "block": bs, "cv_acc": acc})
                lines.append(
                    f"fig5_9,{dataset},K={workers} block={bs},{acc:.4f}")
    _save("fig5_9_parallel_convergence", rows)
    return lines


def fig10_15() -> List[str]:
    """Comm/compute breakdown vs MSF × parallelism (Figs 10–15).

    Paper methodology: instrument around the sync collective. We jit the
    per-block compute and the pmean sync separately (dms_timed_steps) over
    K workers (:func:`_workers`) and time each.
    """
    child = _cpu_child("fig10_15", "fig10_15")
    if child is not None:
        return child

    from repro.launch.mesh import make_test_mesh
    k = _workers()
    mesh = make_test_mesh((k,), ("data",))
    lines = []
    rows = []
    for dataset in ("ijcnn1", "webspam", "epsilon"):
        ds = _ds(dataset)
        n = (ds.n_train // k) * k
        xs = jnp.asarray(ds.x_train[:n].reshape(k, n // k, -1))
        ys = jnp.asarray(ds.y_train[:n].reshape(k, n // k))
        w0 = jnp.zeros(ds.features)
        for bs in (1, 8, 64, 512):
            if (n // k) // bs == 0:
                continue          # dataset too small for this block size
            with jax.set_mesh(mesh):
                compute, sync = svm.dms_timed_steps(mesh, "data",
                                                    block_size=bs)
                nb = (n // k) // bs
                xb = xs[:, :nb * bs].reshape(k, nb, bs, -1)
                yb = ys[:, :nb * bs].reshape(k, nb, bs)
                alpha = jnp.float32(0.5)
                # warmup
                wl = compute(w0, xb[:, 0], yb[:, 0], alpha)
                jax.block_until_ready(sync(wl))
                t_comp = t_sync = 0.0
                blocks = min(nb, 200)
                for i in range(blocks):
                    t0 = time.perf_counter()
                    wl = compute(w0, xb[:, i], yb[:, i], alpha)
                    jax.block_until_ready(wl)
                    t_comp += time.perf_counter() - t0
                    t0 = time.perf_counter()
                    w = sync(wl)
                    jax.block_until_ready(w)
                    t_sync += time.perf_counter() - t0
                # scale to a full epoch's block count
                scale = nb / blocks
                rows.append({"dataset": dataset, "workers": k, "block": bs,
                             "compute_s": t_comp * scale,
                             "comm_s": t_sync * scale,
                             "comm_frac": t_sync / (t_comp + t_sync)})
                lines.append(
                    f"fig10_15,{dataset},K={k} block={bs},"
                    f"comm_frac={t_sync/(t_comp+t_sync):.3f}")
    _save("fig10_15_comm_breakdown", rows)
    return lines


def table2() -> List[str]:
    """Sequential vs parallel timing + accuracy (Table II)."""
    lines = []
    rows = []
    for dataset in ("ijcnn1", "webspam"):
        ds = _ds(dataset)
        x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
        xt, yt = jnp.asarray(ds.x_test), jnp.asarray(ds.y_test)
        w0 = jnp.zeros(ds.features)

        t0 = time.perf_counter()
        w_seq = svm.seq_sgd(w0, x, y, epochs=EPOCHS)
        jax.block_until_ready(w_seq)
        t_seq = time.perf_counter() - t0
        acc_seq = float(svm.accuracy(w_seq, xt, yt))

        t0 = time.perf_counter()
        w_par = svm.dms(w0, ds.x_train, ds.y_train, workers=32,
                        epochs=EPOCHS, block_size=64)
        jax.block_until_ready(w_par)
        t_par = time.perf_counter() - t0
        acc_par = float(svm.accuracy(w_par, xt, yt))

        rows.append({"dataset": dataset, "seq_s": t_seq, "par_s": t_par,
                     "seq_acc": acc_seq, "par_acc": acc_par,
                     "speedup": t_seq / t_par})
        lines.append(f"table2,{dataset},speedup={t_seq/t_par:.1f}x,"
                     f"seq_acc={acc_seq:.4f} par_acc={acc_par:.4f}")
    _save("table2_speedup", rows)
    return lines


def overlap_sweep() -> List[str]:
    """Blocking vs delayed vs chunked sync per-step time across the H ladder.

    The overlap engine's claim (ISSUE 1): delayed/chunked step time ≤
    blocking at every H. Times a jitted scan of dms_block_stepper blocks on
    the synthetic Epsilon stand-in (d=2000 — the sync-bytes-heavy dataset),
    K workers (:func:`_workers`), min over repeats.
    """
    child = _cpu_child("overlap_sweep", "overlap_sweep")
    if child is not None:
        return child

    from repro.launch.mesh import make_test_mesh
    from repro.core import svm as svm_mod
    k = _workers()
    mesh = make_test_mesh((k,), ("data",))
    chunks = 4        # shard count for overlap="chunked" (measured + model)
    rng = np.random.default_rng(0)
    # (label, x (K, n_local, d), y): epsilon is the paper's byte-heavy
    # dataset; "wide64k" makes the sync wire bytes dominate even on host
    # fabrics (d=65536 ⇒ 256 KiB per fp32 sync) so the chunked byte saving
    # is visible where epsilon's d=2000 sync is latency-bound.
    workloads = []
    ds = make_svm_dataset("epsilon", seed=0, n_override=16_384)
    n = (ds.n_train // k) * k
    workloads.append((
        "epsilon", (1, 8, 64, 512),
        jnp.asarray(ds.x_train[:n].reshape(k, n // k, ds.features)),
        jnp.asarray(ds.y_train[:n].reshape(k, n // k))))
    dw, nlw = 65_536, 256
    workloads.append((
        "wide64k", (1, 8, 64),
        jnp.asarray(rng.normal(size=(k, nlw, dw)) / np.sqrt(dw), jnp.float32),
        jnp.asarray(np.where(rng.random((k, nlw)) > 0.5, 1.0, -1.0),
                    jnp.float32)))

    lines, rows = [], []
    with jax.set_mesh(mesh):
        for label, ladder, xs, ys in workloads:
            _, n_local, d = xs.shape
            w0 = jnp.zeros(d)
            alpha = jnp.float32(0.5)
            for h in ladder:
                nb = min(n_local // h, 256)
                if nb == 0:
                    continue
                xb = jnp.swapaxes(
                    xs[:, : nb * h].reshape(k, nb, h, d), 0, 1)  # (nb,K,h,d)
                yb = jnp.swapaxes(ys[:, : nb * h].reshape(k, nb, h), 0, 1)
                runs = {}
                for mode in ("none", "delayed", "chunked"):
                    step = svm_mod.dms_block_stepper(mesh, "data", d=d,
                                                     overlap=mode,
                                                     chunks=chunks)
                    carry0 = svm_mod.dms_stepper_init(w0, k, overlap=mode,
                                                      chunks=chunks)

                    def make_run(step=step, alpha=alpha):
                        @jax.jit
                        def run(carry, xb, yb):
                            def body(c, xy):
                                return step(c, xy[0], xy[1], alpha), None
                            return jax.lax.scan(body, carry, (xb, yb))[0]
                        return run
                    runs[mode] = (make_run(), carry0)
                    jax.block_until_ready(runs[mode][0](carry0, xb, yb))

                # interleave repeats across modes so machine-load drift hits
                # every mode equally; report the min
                best = {mode: float("inf") for mode in runs}
                for _ in range(6):
                    for mode, (run, carry0) in runs.items():
                        t0 = time.perf_counter()
                        jax.block_until_ready(run(carry0, xb, yb))
                        best[mode] = min(best[mode],
                                         time.perf_counter() - t0)
                step_us = {m: b / (nb * h) * 1e6 for m, b in best.items()}
                for mode in ("none", "delayed", "chunked"):
                    lines.append(f"overlap_sweep,{label},H={h} mode={mode},"
                                 f"{step_us[mode]:.2f}")
                rows.append({"dataset": label, "workers": k, "H": h,
                             "blocks": nb, "step_us": step_us})

            # critical-path model rows (mode=model-*): the cost model fed
            # with the measured T_step / T_sync of this workload. On an
            # oversubscribed host CPU the runtime serializes collectives
            # with compute (no true overlap, and barrier latency ≫ wire
            # time), so the measured rows show parity; the model rows show
            # the schedule-level effect the delayed/chunked modes buy on a
            # fabric that can overlap (see also the jaxpr dependency test).
            from repro.config import SyncConfig
            from repro.core import costmodel
            meas = {r["H"]: r["step_us"] for r in rows
                    if r["dataset"] == label}
            if len(meas) >= 2:
                h_max = max(meas)
                t_step = meas[h_max]["none"]
                t_sync = max(0.0, (meas[min(meas)]["none"] - t_step)
                             * min(meas))
                for h in sorted(meas):
                    for mode in ("none", "delayed", "chunked"):
                        t_s = t_sync / (chunks if mode == "chunked" else 1)
                        val = costmodel.overlapped_step_time(
                            t_step, t_s, h, SyncConfig(overlap=mode))
                        lines.append(f"overlap_sweep,{label},"
                                     f"H={h} mode=model-{mode},{val:.2f}")
    _save("overlap_sweep_step_time", rows)
    return lines


def gossip_sweep() -> List[str]:
    """Gossip (ring/pairwise) vs global all-reduce sync — ISSUE 2's claims.

    Section 1 (``bytes`` rows): analytic per-chip wire bytes of one sync
    from the shared cost model across the replica-count ladder. The
    all-reduce moves ``2P(K−1)/K`` (growing toward 2P and paying a global
    barrier); ``ring`` moves a constant ``2P`` to its two neighbors —
    O(1) in K — and ``pairwise`` a constant ``1P``.

    Section 2 (``acc`` rows): accuracy parity on the paper datasets at the
    *autotuner-chosen* H per topology. The tuner's spectral-gap guardrail
    caps gossip H tighter (ring mixes only ``1−λ₂`` per round), which is
    exactly what keeps the gossip accuracy within 0.5% of the global
    baseline. TuneInputs model a slow fabric (comm-bound) so the drift cap
    is the binding constraint — the regime where the guardrail matters.

    Section 3 (``sync_us`` rows): measured per-sync wall time of the
    blocking exchange (dms_timed_steps) over K workers (:func:`_workers`)
    — the gossip exchange does not pay the global barrier.
    """
    from repro.config import SyncConfig
    from repro.core import costmodel
    from repro.core.autotune import TuneInputs, choose_period

    lines, rows = [], []

    # --- 1) analytic wire bytes vs K -----------------------------------
    p_bytes = 2000 * 4          # epsilon's fp32 weight vector, per chip
    for topo in ("all", "ring", "pairwise"):
        for k in (2, 4, 8, 16, 32, 64):
            cfg = SyncConfig(strategy="periodic", topology=topo)
            b = costmodel.wire_bytes_per_sync(p_bytes, k, cfg)
            rows.append({"section": "bytes", "topology": topo, "K": k,
                         "bytes": b})
            lines.append(f"gossip_sweep,bytes,K={k} topo={topo},{b:.0f}")

    # --- 2) accuracy parity at the autotuned H -------------------------
    # For each gossip topology: train at ITS autotuner-chosen H (the
    # spectral-gap guardrail picks a smaller H for sparser mixing) and
    # compare against topology="all" at the SAME H — isolating what the
    # inexact neighbor averaging costs from the paper's own H effect.
    for dataset in ("ijcnn1", "webspam"):
        ds = _ds(dataset)
        k = 8
        xcv, ycv = jnp.asarray(ds.x_cv), jnp.asarray(ds.y_cv)
        w0 = jnp.zeros(ds.features)
        # comm-bound fabric so the spectral-gap drift cap binds: per-step
        # drift 1e-3 ⇒ blocking cap 50 at max_drift=0.05, gossip tighter
        inp = TuneInputs(param_bytes_per_chip=ds.features * 4, replicas=k,
                         step_time_s=1e-6, link_bw=1e6,
                         grad_norm=1.0, param_norm=1.0, lr=1e-3)

        acc_cache = {}

        def acc_at(topo, h, gossip_async=False):
            # memoized: the topology="all" reference at a given H is
            # retrained once, not once per gossip row that shares the H
            key = (topo, h, gossip_async)
            if key not in acc_cache:
                w = svm.dms(w0, ds.x_train, ds.y_train, workers=k,
                            epochs=EPOCHS, block_size=h, topology=topo,
                            gossip_async=gossip_async)
                acc_cache[key] = float(svm.accuracy(w, xcv, ycv))
            return acc_cache[key]

        # async-vs-sync comparison rows: each gossip topology also trains
        # with the unsynchronized-round exchange at ITS tuned H (the
        # staleness-aware spectral-gap cap picks a smaller H), compared
        # against topology="all" at the same H
        for topo, gossip_async in (("all", False), ("ring", False),
                                   ("ring", True), ("pairwise", False),
                                   ("pairwise", True)):
            cfg = SyncConfig(strategy="periodic", topology=topo,
                             gossip_async=gossip_async)
            h = choose_period(inp, cfg, target_overhead=0.05, max_drift=0.05)
            acc = acc_at(topo, h, gossip_async)
            acc_ref = acc if topo == "all" else acc_at("all", h)
            mode = f"{topo}{'_async' if gossip_async else ''}"
            rows.append({"section": "acc", "dataset": dataset,
                         "topology": topo, "gossip_async": gossip_async,
                         "H": h, "cv_acc": acc,
                         "spectral_gap": costmodel.effective_spectral_gap(
                             k, topo, staleness=1 if gossip_async else 0)
                         if topo != "all"
                         else costmodel.spectral_gap(k, topo),
                         "delta_vs_all_same_h": acc - acc_ref})
            lines.append(f"gossip_sweep,acc,{dataset} topo={mode} H={h},"
                         f"{acc:.4f} (Δ@H={acc - acc_ref:+.4f})")

    # --- 3) measured per-sync time over K workers ----------------------
    child = _cpu_child("gossip_sweep_timing", "gossip_sweep")
    lines += gossip_sweep_timing() if child is None else child
    _save("gossip_sweep", rows)
    return lines


def gossip_sweep_timing() -> List[str]:
    """Measured blocking-sync wall time per topology over K workers."""
    from repro.launch.mesh import make_test_mesh
    k, d = _workers(), 65_536   # wide model: sync bytes dominate latency
    if k < 2:
        return ["gossip_sweep,sync_us,SKIP,needs 2+ workers"]
    mesh = make_test_mesh((k,), ("data",))
    rng = np.random.default_rng(0)
    w_locals = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    cnt = jnp.zeros((), jnp.int32)
    lines, rows = [], []
    with jax.set_mesh(mesh):
        for topo, gossip_async in (("all", False), ("ring", False),
                                   ("ring", True), ("pairwise", False),
                                   ("pairwise", True)):
            if topo == "pairwise" and k % 2:
                continue          # pairwise needs an even worker count
            _, sync = svm.dms_timed_steps(mesh, "data", block_size=8,
                                          topology=topo,
                                          gossip_async=gossip_async)
            if gossip_async:
                sent, mixbuf = svm.dms_async_buffers_init(w_locals, topo)
                run = lambda: sync(w_locals, sent, mixbuf, cnt)
            elif topo == "all":
                run = lambda: sync(w_locals)
            else:
                run = lambda: sync(w_locals, cnt)
            jax.block_until_ready(run())
            best = float("inf")
            for _ in range(20):
                t0 = time.perf_counter()
                jax.block_until_ready(run())
                best = min(best, time.perf_counter() - t0)
            mode = f"{topo}{'_async' if gossip_async else ''}"
            rows.append({"section": "sync_us", "topology": topo,
                         "gossip_async": gossip_async,
                         "K": k, "d": d, "sync_us": best * 1e6})
            lines.append(f"gossip_sweep,sync_us,K={k} topo={mode},"
                         f"{best*1e6:.1f}")
    _save("gossip_sweep_timing", rows)
    return lines


def hinge_kernel() -> List[str]:
    """Fused Pallas hinge block-gradient vs the jnp reference (hot path).

    With the interpret default fixed (auto: compiled on TPU/GPU, interpreter
    only on CPU) this times the compiled kernel on accelerators; on CPU the
    interpreter is orders slower, so the problem is shrunk to keep the
    suite fast and the row is labeled ``interpret``.
    """
    from repro.core.svm import block_grad
    from repro.kernels import default_interpret
    from repro.kernels.hinge import ops as hinge_ops
    interp = default_interpret()
    n, d = (256, 128) if interp else (4096, 2048)
    reps = 3 if interp else 20
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(np.where(rng.random(n) > 0.5, 1.0, -1.0), jnp.float32)
    w = jnp.asarray(rng.normal(size=d), jnp.float32)

    g_ref = block_grad(w, x, y, 1.0, "jnp")
    g_pal = hinge_ops.hinge_block_grad(w, x, y, 1.0)
    err = float(jnp.max(jnp.abs(g_ref - g_pal)))
    assert err < 1e-3, err

    def best_of(fn):
        jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    mode = "interpret" if interp else "compiled"
    t_ref = best_of(lambda: block_grad(w, x, y, 1.0, "jnp"))
    t_pal = best_of(lambda: hinge_ops.hinge_block_grad(w, x, y, 1.0))
    rows = [{"mode": mode, "n": n, "d": d, "ref_us": t_ref,
             "pallas_us": t_pal, "max_abs_err": err}]
    _save("hinge_kernel_bench", rows)
    return [f"hinge_kernel,ref,n={n} d={d},{t_ref:.1f}",
            f"hinge_kernel,pallas-{mode},n={n} d={d},{t_pal:.1f}"]


ALL = {"fig1_3": fig1_3, "fig2_4": fig2_4, "fig5_9": fig5_9,
       "fig10_15": fig10_15, "table2": table2,
       "overlap_sweep": overlap_sweep, "gossip_sweep": gossip_sweep,
       "gossip_sweep_timing": gossip_sweep_timing,
       "hinge_kernel": hinge_kernel}


if __name__ == "__main__":
    import sys
    which = sys.argv[1:] or list(ALL)
    for name in which:
        for line in ALL[name]():
            print(line)
