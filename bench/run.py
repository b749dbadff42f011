"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``bench/workloads/<cell>.json``,
its configuration in ``bench/configs/<config>.json``, the driver that runs
that configuration's program path in ``bench/drivers/<driver>.py``, each
per-layer metric's reader in ``bench/metrics/<metric>.py``, and which
metrics a cell reports, with their units, in ``BENCHMARK.json``.

The first work is a look for the chips: with no TPU, or fewer chips than
the cell asks for, the run exits non-zero and prints no result. A driver
sets up (data, weights and state made on the device from the seed,
compiles, warm-up), measures for ``--seconds``, then checks what the
timed path produced against a plain reference. With ``--trace 1`` it
profiles a few units of work inside the window and hands over the profile
and, read after the traced stretch, the HLO text of the programs that ran
in it; the harness reduces them to the summary the per-layer readers get.
A reader returns nothing where it finds nothing to read, and its metric
is then left out of the line. Earlier lines of standard output carry the
driver's records; the last is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``) and, last, ``check``: each number compared beside
its limit, which also ends standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up is timed from process start

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the checkout's root (for ``bench``) and the program's sources; not this
# file's directory, whose trace.py would shadow the standard library's
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def require_devices(chips: int) -> list:
    """The TPU chips the cell runs on; exits non-zero where there are
    none or too few. Never falls back to another platform."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU; JAX found {devices[0].platform} "
                 "devices only")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chips; JAX found "
                 f"{len(devices)}")
    return devices[:chips]


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, by its file name."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, its configuration, the run's
    arguments and devices, and where to log."""
    name: str
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    t0: float
    log: Callable[..., None]


@dataclasses.dataclass
class Result:
    """What a driver returns."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]      # by metric name, in its unit
    counts: Dict[str, Any]            # for the per-layer readers
    checks: List[dict]                # {"name", "value", "limit"}
    memory_peak_bytes: int
    # with --trace 1: the profile of the traced stretch, and the compiled
    # HLO text of each program that ran in it, read after that stretch
    trace_dir: Optional[str] = None
    programs: Optional[List[str]] = None


@dataclasses.dataclass
class Reading:
    """What a per-layer reader gets."""
    summary: Optional[dict]
    counts: Dict[str, Any]
    peaks: dict
    chips: int
    config: dict
    cell: dict


def log(**kw) -> None:
    print(json.dumps(kw), flush=True)


def cell_metrics(spec: dict, name: str):
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` gives the
    cell."""
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in names
                              else [])]
    return e2e, layer


def summarize_trace(trace_dir: str, programs: Optional[List[str]]) -> dict:
    """The summary the per-layer readers get: ``bench.trace.summarize``'s,
    and, where the driver handed the programs' text, ``scopes``,
    ``host_spans`` and ``unmapped`` (``bench.scopes.layer_summary``).
    The profile is removed once read."""
    from bench import scopes as sc, trace as tr
    try:
        path = tr.find_xplane(trace_dir)
        plain = tr.load(path)
        summary = tr.summarize(plain)
        if programs is not None:
            summary.update(sc.layer_summary(sc.scoped(plain, path,
                                                      programs)))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


def check_ok(c: dict) -> bool:
    """Within its limit; a limit not yet set from readings (null in the
    cell's file) holds nothing as correct."""
    v, limit = c["value"], c["limit"]
    return (limit is not None and v == v and abs(v) != float("inf")
            and v <= limit)


def run_cell(name: str, seed: int, seconds: float, trace: bool) -> dict:
    """Run one cell and return the result object (the last line)."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = load_json(BENCH, "workloads", name + ".json")
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    devices = require_devices(cell["chips"])

    from repro.launch.cache import use_compile_cache
    import jax
    cache = use_compile_cache()
    # every program of the cell goes to the cache, so that runs after the
    # first compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(phase="start", workload=name, seed=seed, seconds=seconds,
        trace=trace, compile_cache=cache, kind=devices[0].device_kind,
        count=len(devices))

    e2e, layer = cell_metrics(spec, name)
    driver = load_module("drivers", config["driver"])
    ctx = Context(name, cell, config, seed, seconds, trace, devices, T0, log)
    res: Result = driver.run(ctx)

    from bench import peaks as peaks_mod
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(res.memory_peak_bytes)}
    metrics = {}
    breakdown = None
    if trace:
        s = summarize_trace(res.trace_dir, res.programs)
        log(phase="trace", busy_s=s["mean"]["busy_s"],
            unmapped_s=s.get("unmapped"), scopes=s.get("scopes"),
            host_spans=s.get("host_spans"))
        device["busy_s"] = s["mean"]["busy_s"]
        device["window_s"] = s["window_s"]
        reading = Reading(s, res.counts, peaks_mod.lookup(
            devices[0].device_kind), len(devices), config, cell)
        for m in layer:
            value = load_module("metrics", m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": s["device_ops"],
                     "idle_gaps": s["idle_gaps"]}
    else:
        for m in e2e:
            if m["name"] not in res.end_to_end:
                raise KeyError(f"driver {config['driver']} did not report "
                               f"{m['name']}")
            metrics[m["name"]] = {"value": res.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    correct = (res.attempted > 0 and res.failed == 0
               and all(check_ok(c) for c in res.checks))
    out = {"correct": correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                    for c in res.checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
