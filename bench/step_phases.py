"""``bench/run.py`` with each LM step's host phases timed.

    python3 bench/step_phases.py --out FILE --workload <cell> --seed <n>
                                 --seconds 30 --trace 0

Runs ``bench.run.main`` in this process with the other arguments, so it
prints the same result line. Around it, each host span that
``repro.runtime.StepRunner`` opens in a step (``repro.step`` and, inside
it, ``data``, ``dispatch`` and ``fetch``) is also timed on
``time.perf_counter``, and each of the interpreter's garbage collections
over 1 ms that runs inside a step is noted (``gc.callbacks``). ``--out``
gets one JSON line a step: its number, its seconds, each phase's seconds
and the collections (generation, seconds). A stalled step then says
whether it waited for its batch, in the dispatch, or on the device.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)                  # this directory's trace.py shadows
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


class Phases:
    """Host seconds of each step and of the spans inside it."""

    def __init__(self, least_gc_s: float = 1e-3):
        self.steps: list = []
        self.least_gc_s = least_gc_s
        self._now = None             # the open step's row
        self._gc_t0 = None

    def step_span(self, opened):
        """``opened(step)`` (``repro.core.telemetry.step_span``), timed."""
        @contextlib.contextmanager
        def step_span(step):
            row = {"step": step}
            self._now, t0 = row, time.perf_counter()
            try:
                with opened(step):
                    yield
            finally:
                row["step_s"] = time.perf_counter() - t0
                self.steps.append(row)
                self._now = None
        return step_span

    def span(self, opened):
        """``opened(name, **ids)`` (``repro.core.telemetry.span``), its
        seconds added to the open step's row under ``name``."""
        @contextlib.contextmanager
        def span(name, **ids):
            row, t0 = self._now, time.perf_counter()
            try:
                with opened(name, **ids):
                    yield
            finally:
                if row is not None:
                    row[name] = row.get(name, 0.0) + time.perf_counter() - t0
        return span

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        if self._gc_t0 is None or self._now is None:
            return
        took = time.perf_counter() - self._gc_t0
        if took >= self.least_gc_s:
            self._now.setdefault("gc", []).append([info["generation"], took])


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--out")
    dest = argv[i + 1]
    del argv[i:i + 2]
    from bench import run
    from repro.runtime import ft
    phases = Phases()
    opened = ft.span, ft.step_span
    ft.span, ft.step_span = phases.span(ft.span), phases.step_span(ft.step_span)
    gc.callbacks.append(phases.on_gc)
    try:
        rc = run.main(argv)
    finally:
        ft.span, ft.step_span = opened
        gc.callbacks.remove(phases.on_gc)
        with open(dest, "w") as f:
            for row in phases.steps:
                f.write(json.dumps(row) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
