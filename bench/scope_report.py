"""Run a cell as ``bench/run.py`` runs it with ``--trace 1``, and read its
trace by the names the program gives its work.

    python3 bench/scope_report.py --workload <cell> --seed <n>
                                  [--seconds 10] [--record FILE]

The run is ``bench.run.run_cell``'s, untouched: the same driver, window,
traced stretch and check. Two things are kept on the side. Every program
compiled in the run gives its HLO text (the process compiles them all:
the persistent compile cache is off, so the text is the compiler's own).
And when the driver loads its trace with ``bench.trace.load``, the same
trace is read by scope (``bench.scopes.scoped``).

The one JSON line printed holds the cell's result line (``result``, as
``bench/run.py`` prints it), the job or step accounting (``per_unit``),
what one traced unit reads per scope (``readings``), how far each device
module run lies outside its host interval once the clocks are aligned
(``outside_units``), the trace file's size and the scope summary.
``--record`` writes a trimmed ``bench.scopes.Scoped`` for the tests:
eight whole SVM blocks from the middle of the first traced job, or the
longest operations of each scope in one LM step with that step's spans.
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

from bench import run as R
from bench import scopes as sc
from bench import trace as tr


def _hlo_text(exe) -> str:
    """A loaded executable's HLO text with its metadata, as
    ``jax.stages.Compiled.as_text`` reads it."""
    if hasattr(exe, "get_hlo_text"):
        return exe.get_hlo_text()
    return "\n\n".join(m.to_string() for m in exe.hlo_modules())


def per_unit(cell: dict, config: dict) -> dict:
    """What one traced unit does: an SVM job's blocks and exchanges on
    each worker (``repro.core.svm.dms_job_counts``), or one LM step."""
    t = cell["traffic_params"]
    if config["driver"] == "svm_dms":
        from repro.core import svm
        n_local = int(config["samples"] * config["split"]["train"]) \
            // cell["chips"]
        return svm.dms_job_counts(
            n_local, config["features"], t["block_per_worker"],
            t["epochs_per_job"], overlap=t["overlap"],
            topology=t["topology"])
    return {"steps": 1, "tokens": t["global_batch"] * t["seq_len"]}


def readings(summary: dict, per: dict, units: int) -> dict:
    """What one unit reads by the program's names: SVM microseconds a
    sync and a block, LM milliseconds a step."""
    s, h = summary["scopes"], summary["host_spans"]
    if "syncs" in per:
        return {"svm_sync_us": s.get("svm.sync", 0.0)
                / (units * per["syncs"]) * 1e6,
                "svm_block_us": s.get("svm.block", 0.0)
                / (units * per["blocks"]) * 1e6}
    return {"attn_ms": s.get("lm.attention", 0.0) / units * 1e3,
            "opt_ms": s.get("lm.optimizer", 0.0) / units * 1e3,
            "dispatch_ms": h.get("dispatch", 0.0) / units * 1e3}


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


def report(name: str, seed: int, seconds: float = 10.0,
           record=None) -> dict:
    import jax
    from jax._src import compiler

    texts, kept = [], {}
    compile_program = compiler.compile_or_get_cached
    load_trace = tr.load

    def compile_and_keep(*args, **kwargs):
        exe = compile_program(*args, **kwargs)
        texts.append(_hlo_text(exe))
        return exe

    def load_and_keep(path):
        plain = load_trace(path)
        kept["scoped"] = sc.scoped(plain, path, texts)
        kept["bytes"] = os.path.getsize(path)
        return plain

    cache = jax.config.jax_enable_compilation_cache
    compiler.compile_or_get_cached = compile_and_keep
    tr.load = load_and_keep
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        result = R.run_cell(name, seed, seconds, True)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compiler.compile_or_get_cached = compile_program
        tr.load = load_trace
    cell = R.load_json(R.BENCH, "workloads", name + ".json")
    config = R.load_json(R.BENCH, "configs", cell["config"] + ".json")
    is_svm = config["driver"] == "svm_dms"
    units = cell["traffic_params"]["traced_jobs" if is_svm
                                   else "traced_steps"]
    scoped = kept["scoped"]
    summary = sc.summarize(scoped)
    per = per_unit(cell, config)
    if is_svm:
        inside = [s[1:] for s in scoped.spans
                  if s[0] == tr.SPAN_PREFIX + "job"]
    else:
        inside = sc.dispatch_to_fetch(scoped.spans)
    if record:
        cut = (trim_svm if is_svm else trim_lm)(scoped)
        with open(record, "w") as f:
            f.write(cut.to_json())
    return {"workload": name, "seed": seed, "result": result,
            "units": units, "per_unit": per,
            "readings": readings(summary, per, units),
            "outside_units": sc.outside_units(scoped, inside),
            "trace_bytes": kept["bytes"], "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record")
    args = ap.parse_args(argv)
    print(json.dumps(report(args.workload, args.seed, args.seconds,
                            args.record)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
