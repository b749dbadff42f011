"""Smoke run of the system's two entry points on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host

One chip: the paper's DMS-SVM (``repro.core.svm.dms``, shard_map backend)
on the Table I epsilon stand-in (400,000 x 2,000, 320,000 training rows),
one epoch at block size 64, with the Pallas hinge kernel and with jnp, each
checked against an SRDMS (Algorithm 2) reference; then the LM trainer
(``repro.launch.train``) on smollm-360m at full width for a few steps.

Four chips: DMS over a 4-chip ``data`` mesh with all-reduce and ring
gossip, each checked against the same K=4 run under ``backend="vmap"`` on
one device; then local SGD over 4 replicas (``sync.period=4``), whose
replicas must be equal after the final flush.

Every phase runs in this process and raises on failure. Phase lines
(seconds are set-up and smoke timings, not performance) go to standard
output; its last line is ``{"ok": true, "device": {...}}``, printed only
when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

BLOCK = 64          # points per worker per sync
ACC_PP = 0.005      # test accuracy within 0.5 percentage points
# Weight tolerance (relative L2). Every margin is computed in float32
# passes (Precision.HIGHEST), so while two runs pick the same violators in
# every block they differ only by float32 rounding of one block's sum
# (~1e-7): with a step size of 1 in the first epoch, w is replaced by each
# block's violator mean. One differing violator moves w by a whole sample
# (~|x|/64, ~15% on epsilon). 1e-4 sits between the two.
W_RTOL = 1e-4


def log(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


def check_weights(name: str, w, w_ref, acc: float, acc_ref: float) -> None:
    import numpy as np
    w, w_ref = np.asarray(w, np.float64), np.asarray(w_ref, np.float64)
    rel = float(np.linalg.norm(w - w_ref) / np.linalg.norm(w_ref))
    log(name + "_check", rel_l2=rel, rtol=W_RTOL, acc=acc, acc_ref=acc_ref)
    if not np.isfinite(w).all():
        raise AssertionError(f"{name}: non-finite weights")
    if not rel <= W_RTOL:
        raise AssertionError(f"{name}: rel L2 {rel} > {W_RTOL}")
    if not abs(acc - acc_ref) <= ACC_PP:
        raise AssertionError(f"{name}: accuracy {acc} vs {acc_ref}")


def require_kernel(hlo: str) -> None:
    if "tpu_custom_call" not in hlo:
        raise AssertionError("hinge kernel is not a tpu_custom_call")


def epsilon(seed: int):
    from repro.data import make_svm_dataset
    t = time.perf_counter()
    ds = make_svm_dataset("epsilon", seed=seed)
    log("svm_synthesis", seconds=time.perf_counter() - t,
        train_rows=ds.n_train, features=ds.features)
    return ds


def svm_one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import svm

    ds = epsilon(seed)
    n, d = ds.x_train.shape
    w0 = jnp.zeros((d,), jnp.float32)
    xt, yt = jnp.asarray(ds.x_test), jnp.asarray(ds.y_test)

    t = time.perf_counter()
    x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
    with jax.default_matmul_precision("highest"):
        w_ref = svm.srdms(w0, x, y, epochs=1, block_size=BLOCK)
        acc_ref = float(svm.accuracy(w_ref, xt, yt))
    del x, y
    log("svm_reference", seconds=time.perf_counter() - t, acc=acc_ref)

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    for impl in ("pallas", "jnp"):
        fn = svm.dms_shard_map_program(mesh, "data", epochs=1,
                                       block_size=BLOCK, grad_impl=impl)
        t = time.perf_counter()
        compiled = fn.lower(
            w0, jax.ShapeDtypeStruct((1, n, d), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=rows)
        ).compile()
        t_compile = time.perf_counter() - t
        if impl == "pallas":
            require_kernel(compiled.as_text())
        t = time.perf_counter()
        w = svm.dms(w0, ds.x_train, ds.y_train, workers=1, epochs=1,
                    block_size=BLOCK, grad_impl=impl, backend="shard_map",
                    mesh=mesh)
        w.block_until_ready()
        # run seconds include the 2.56 GB host-to-device placement
        log(f"svm_dms_{impl}", compile_seconds=t_compile,
            run_seconds=time.perf_counter() - t)
        check_weights(f"svm_dms_{impl}", w, w_ref,
                      float(svm.accuracy(w, xt, yt)), acc_ref)


def svm_four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import svm

    ds = epsilon(seed)
    w0 = jnp.zeros((ds.features,), jnp.float32)
    xt, yt = jnp.asarray(ds.x_test), jnp.asarray(ds.y_test)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    for topology in ("all", "ring"):
        kw = dict(workers=4, epochs=1, block_size=BLOCK, topology=topology)
        t = time.perf_counter()
        w_ref = svm.dms(w0, ds.x_train, ds.y_train, backend="vmap", **kw)
        acc_ref = float(svm.accuracy(w_ref, xt, yt))
        log(f"svm_vmap_{topology}", seconds=time.perf_counter() - t,
            acc=acc_ref)
        t = time.perf_counter()
        w = svm.dms(w0, ds.x_train, ds.y_train, backend="shard_map",
                    mesh=mesh, **kw)
        w.block_until_ready()
        log(f"svm_shard_map_{topology}", seconds=time.perf_counter() - t)
        check_weights(f"svm_shard_map_{topology}", w, w_ref,
                      float(svm.accuracy(w, xt, yt)), acc_ref)


def lm(seed: int, extra) -> dict:
    import math
    from repro.launch import train
    argv = ["--arch", "smollm-360m",
            "--set", "optimizer.name=adamw",
            "--set", "optimizer.learning_rate=0.001",
            "--set", f"seed={seed}", "--set", f"data.seed={seed}", *extra]
    t = time.perf_counter()
    out, state = train.train(argv)
    log("lm_train", seconds=time.perf_counter() - t, **out)
    first, last = out["first_loss"], out["last_loss"]
    if not (math.isfinite(first) and math.isfinite(last) and last < first):
        raise AssertionError(f"loss did not fall: {first} -> {last}")
    return state


def lm_four_replicas(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    state = lm(seed, ["--steps", "3", "--set", "sync.strategy=periodic",
                      "--set", "sync.period=4",
                      "--set", "mesh.replica_axis=data"])
    spread = jax.jit(lambda ps: jnp.max(jnp.stack(
        [jnp.max(jnp.abs(p - p[:1])) for p in jax.tree.leaves(ps)])))
    worst = float(spread(state["params"]))
    reps = {p.shape[0] for p in jax.tree.leaves(state["params"])}
    log("lm_replicas", replicas=sorted(reps), max_abs_diff=worst)
    if reps != {4} or worst != 0.0:
        raise AssertionError(f"replicas differ after the flush: {worst}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU; JAX found {devices[0].platform} "
                 "devices only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs that many TPU "
                 f"chips; JAX found {len(devices)}")
    from repro.launch.cache import use_compile_cache
    log("setup", compile_cache=use_compile_cache(),
        kind=devices[0].device_kind, count=len(devices))

    if args.chips == 1:
        svm_one_chip(args.seed)
        lm(args.seed, ["--steps", "6"])
    else:
        svm_four_chips(args.seed)
        lm_four_replicas(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
