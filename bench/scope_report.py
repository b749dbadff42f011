"""Run a cell as ``bench/run.py`` runs it with ``--trace 1``, and keep its
trace as read by the names the program gives its work.

    python3 bench/scope_report.py --workload <cell> --seed <n>
                                  [--seconds 10] [--record FILE]
                                  [--gaps FILE]

The run is ``bench.run.run_cell``'s, untouched: the same driver, window,
traced stretch, check and per-layer metrics, the readings by scope among
them. The trace, as the harness reads it by scope
(``bench.scopes.scoped``), is kept on the side.

The one JSON line printed holds the cell's result line (``result``, as
``bench/run.py`` prints it), the job or step accounting (``per_unit``),
how far each device module run lies outside its host interval once the
clocks are aligned (``outside_units``), the trace file's size and the
whole scope summary (``bench.scopes.summarize``: op counts and exchanges
by scope beside the seconds). ``--record`` writes a trimmed
``bench.scopes.Scoped`` for the tests: eight whole SVM blocks from the
middle of the first traced job, or the longest operations of each scope
in one LM step with that step's spans. ``--gaps`` writes, as JSON, every
device idle gap over 0.1 ms of the traced stretch, longest first, with
the operations on either side of it and the module run it falls inside.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)                  # this directory's trace.py shadows
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import counts
from bench import run as R
from bench import scopes as sc
from bench import trace as tr


def per_unit(cell: dict, config: dict) -> dict:
    """What one traced unit does: an SVM job's blocks and exchanges on
    each worker (``bench.counts.svm_job_counts``), or one LM step."""
    t = cell["traffic_params"]
    if config["driver"] == "svm_dms":
        n_local = int(config["samples"] * config["split"]["train"]) \
            // cell["chips"]
        return counts.svm_job_counts(
            n_local, t["block_per_worker"], t["epochs_per_job"],
            t["overlap"], t["topology"])
    return {"steps": 1, "tokens": t["global_batch"] * t["seq_len"]}


def trim_svm(t: sc.Scoped, blocks: int = 8) -> sc.Scoped:
    """Eight whole blocks from the middle of the first traced job: the
    stretch between two of the first device's exchanges ``blocks``
    apart."""
    first = t.devices[min(t.devices)]
    job = min((s for s in t.spans if s[0] == tr.SPAN_PREFIX + "job"
               and s[1] >= tr.window(t.plain())[0]), key=lambda s: s[1])
    colls = [op for op in first if sc.exchange(op[3])
             and job[1] <= op[1] < job[2]]
    mid = len(colls) // 2
    lo, hi = colls[mid][2], colls[mid + blocks][2]
    return _cut(t, lo, hi, lambda ops: ops)


def trim_lm(t: sc.Scoped, per_scope: int = 30) -> sc.Scoped:
    """One step (the second traced): its spans, its module run, and the
    ``per_scope`` longest operations of each scope in it."""
    steps = sorted((s for s in t.spans if s[0] == tr.SPAN_PREFIX + "step"
                    and s[1] >= tr.window(t.plain())[0]),
                   key=lambda s: s[1])
    _, lo, hi = steps[min(1, len(steps) - 1)]

    def longest(ops):
        keep = []
        for scope in sorted({op[4] for op in ops}):
            mine = [op for op in ops if op[4] == scope]
            keep += sorted(mine, key=lambda op: op[1] - op[2])[:per_scope]
        return sorted(keep, key=lambda op: op[1])
    return _cut(t, lo, hi, longest)


def _cut(t: sc.Scoped, lo: int, hi: int, pick) -> sc.Scoped:
    """The part of ``t`` in [lo, hi), with ``bench.traced`` moved there
    and operation names cut as ``bench.trace.short_name`` cuts them."""
    spans = [s for s in t.spans if s[2] > lo and s[1] < hi
             and s[0] != tr.TRACED] + [(tr.TRACED, lo, hi)]
    return sc.Scoped(
        {d: [(tr.short_name(op[0]),) + tuple(op[1:]) for op in
             pick([op for op in ops if op[2] > lo and op[1] < hi])]
         for d, ops in t.devices.items()},
        {d: [r for r in runs if r[2] > lo and r[1] < hi]
         for d, runs in t.modules.items()},
        sorted(spans, key=lambda s: s[1]))


def gaps(t: sc.Scoped, least_ns: int = 100_000) -> list:
    """Each device's idle gaps longer than ``least_ns``, longest first:
    the ms, the operations before and after, and the module run the gap
    lies inside (empty between runs)."""
    out = []
    for dev, ops in t.devices.items():
        runs = t.modules.get(dev, [])
        end, prev = None, None
        for op in sorted(ops, key=lambda o: o[1]):
            if end is not None and op[1] - end > least_ns:
                inside = [r[0] for r in runs if r[1] <= end and op[1] <= r[2]]
                out.append({"dev": dev, "ms": (op[1] - end) / 1e6,
                            "after": str(prev[0])[:90],
                            "before": str(op[0])[:90],
                            "in_module_run": inside[:1]})
            if end is None or op[2] > end:
                end, prev = op[2], op
    return sorted(out, key=lambda g: -g["ms"])


def report(name: str, seed: int, seconds: float = 10.0,
           record=None, gaps_to=None) -> dict:
    kept = {}
    scoped = sc.scoped

    def keep(plain, path, texts):
        kept["scoped"] = out = scoped(plain, path, texts)
        kept["bytes"] = os.path.getsize(path)
        return out

    sc.scoped = keep
    try:
        result = R.run_cell(name, seed, seconds, True)
    finally:
        sc.scoped = scoped
    cell = R.load_json(R.BENCH, "workloads", name + ".json")
    config = R.load_json(R.BENCH, "configs", cell["config"] + ".json")
    is_svm = config["driver"] == "svm_dms"
    units = cell["traffic_params"]["traced_jobs" if is_svm
                                   else "traced_steps"]
    t = kept["scoped"]
    if is_svm:
        inside = [s[1:] for s in t.spans if s[0] == tr.SPAN_PREFIX + "job"]
    else:
        inside = sc.dispatch_to_fetch(t.spans)
    if record:
        cut = (trim_svm if is_svm else trim_lm)(t)
        with open(record, "w") as f:
            f.write(cut.to_json())
    if gaps_to:
        with open(gaps_to, "w") as f:
            json.dump(gaps(t), f, indent=0)
    return {"workload": name, "seed": seed, "result": result,
            "units": units, "per_unit": per_unit(cell, config),
            "outside_units": sc.outside_units(t, inside),
            "trace_bytes": kept["bytes"], "summary": sc.summarize(t)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record")
    ap.add_argument("--gaps")
    args = ap.parse_args(argv)
    print(json.dumps(report(args.workload, args.seed, args.seconds,
                            args.record, args.gaps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
