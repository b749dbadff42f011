"""Benchmark entrypoint: ``python -m benchmarks.run [sweeps...] [--json]``.

One registry for every sweep — the paper-figure reproductions
(:mod:`benchmarks.paper_figs`), the simulated sync-schedule sweep
(:mod:`benchmarks.simsync_sweep`) and the roofline summary — dispatched
behind a single CLI. Each sweep prints ``name,label,value[,derived]`` CSV
lines; ``--json`` additionally bundles everything a sweep recorded (its
CSV lines plus every structured record section it saved) into one
``BENCH_<sweep>.json`` under ``--out``, so benchmark trajectories are
captured uniformly across sweeps.

    python -m benchmarks.run --list
    python -m benchmarks.run hinge_kernel overlap_sweep
    python -m benchmarks.run simsync_sweep --json --out experiments/bench
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Callable, Dict, List


def _roofline() -> List[str]:
    """Roofline summary assembled from the dry-run artifacts (if present)."""
    from benchmarks import roofline_table
    if not os.path.isdir("experiments/dryrun"):
        return ["roofline,SKIP,,no experiments/dryrun artifacts"]
    return list(roofline_table.csv_lines(roofline_table.load()))


def registry() -> Dict[str, Callable[[], List[str]]]:
    from benchmarks import adaptive_trainer, paper_figs, simsync_sweep
    reg: Dict[str, Callable[[], List[str]]] = dict(paper_figs.ALL)
    reg["simsync_sweep"] = simsync_sweep.run
    reg["adaptive_trainer"] = adaptive_trainer.run
    reg["roofline"] = _roofline
    return reg


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("sweeps", nargs="*",
                    help="sweep names (default: all registered sweeps)")
    ap.add_argument("--list", action="store_true",
                    help="list registered sweeps and exit")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<sweep>.json bundles under --out")
    ap.add_argument("--out", default="experiments/bench",
                    help="output directory for --json bundles")
    args = ap.parse_args(argv)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    reg = registry()
    if args.list:
        for name in sorted(reg):
            print(name)
        return

    names = args.sweeps or [n for n in reg if n != "roofline"]
    unknown = [n for n in names if n not in reg]
    if unknown:
        ap.error(f"unknown sweep(s) {unknown}; known: {sorted(reg)}")

    from benchmarks import record
    for name in names:
        record.take_saved()          # drop any stale registrations
        lines = reg[name]()
        for line in lines:
            print(line)
        if args.json:
            os.makedirs(args.out, exist_ok=True)
            bundle = {"sweep": name, "csv": lines,
                      "records": record.take_saved()}
            path = os.path.join(args.out, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(bundle, f, indent=1)
            print(f"{name},BENCH,,{path}")

    # historical default: append the roofline summary when the dry-run
    # artifacts exist and it wasn't explicitly requested
    if "roofline" not in names and os.path.isdir("experiments/dryrun"):
        for line in _roofline():
            print(line)


if __name__ == "__main__":
    main()
