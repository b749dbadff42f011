"""``bench/scope_report.py`` with each HLO instruction's text on one line.

    python3 bench/scope_joined.py --workload <cell> --seed <n>
                                  [--seconds 10] [--record FILE]
                                  [--gaps FILE]

A Mosaic kernel's instruction (a ``tpu_custom_call``, such as the splash
attention kernels) carries a ``kernel_metadata`` block whose value runs
over three lines ahead of its ``metadata={op_name=...}``.
``bench.scopes.op_table`` reads one line per instruction, so it reads such
a kernel as ``unscoped``. This runs ``bench.scope_report`` unchanged,
except that each such block is joined onto its instruction's line before
the table is made. ``--gaps FILE`` also writes, as JSON, every device idle
gap over 0.1 ms of the traced stretch, longest first, with the operations
on either side of it and the module run it falls inside.
"""
from __future__ import annotations

import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)                  # this directory's trace.py shadows
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import scopes as sc

_BLOCK = re.compile(r"\{\n([^\n]*)\n\}\}")


def joined(text: str) -> str:
    """``text`` with each multi-line ``{…}}`` attribute block on the line
    of the instruction it belongs to."""
    return _BLOCK.sub(r"{\1}}", text)


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


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    dest = None
    if "--gaps" in argv:
        i = argv.index("--gaps")
        dest = argv[i + 1]
        del argv[i:i + 2]
    from bench import scope_report
    op_table, scoped, kept = sc.op_table, sc.scoped, {}

    def keep(*args, **kwargs):
        kept["scoped"] = out = scoped(*args, **kwargs)
        return out

    sc.op_table = lambda texts: op_table([joined(t) for t in texts])
    sc.scoped = keep
    try:
        rc = scope_report.main(argv)
    finally:
        sc.op_table, sc.scoped = op_table, scoped
    if dest and "scoped" in kept:
        with open(dest, "w") as f:
            json.dump(gaps(kept["scoped"]), f, indent=0)
    return rc


if __name__ == "__main__":
    sys.exit(main())
