"""Read a cell's control and its planted faults on the chip.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed, one JSON line: the numbers the cell compares, read for the
control (the plain reference computed one precision step below what the
configuration states) and for each fault planted under the timed path,
against the reference at the cell's own size. These readings, beside the
program's over a dozen seeds, set each limit in the cell's file. The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"),
                os.path.dirname(BENCH)]

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = R.load_json(R.BENCH, "workloads", args.workload + ".json")
    config = R.load_json(R.BENCH, "configs", cell["config"] + ".json")
    devices = R.require_devices(cell["chips"])
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    driver = R.load_module("drivers", config["driver"])
    for seed in args.seeds:
        ctx = R.Context(args.workload, cell, config, seed, 0.0, False,
                        devices, R.T0, R.log)
        R.log(phase="control", seed=seed,
              readings=driver.control_readings(ctx))
    return 0


if __name__ == "__main__":
    sys.exit(main())
