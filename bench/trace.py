"""Reduce a profiler trace to what the per-layer metrics read.

``load`` walks an ``.xplane.pb`` (``jax.profiler.ProfileData``) and keeps
two things: the leaf operations each device ran (name, start, end in ns)
and the benchmark's own host spans (``TraceAnnotation`` events whose
names start with ``bench.``). ``summarize`` then works on those lists
alone, so a trimmed recording (``Trace.to_json``) tests the arithmetic:
the union of busy intervals, the split into collective and other
operations, the collective time no other operation overlaps, and idle
gaps labelled with the host span open in each.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax

SPAN_PREFIX = "bench."
# the traced stretch of a run: the window inside which shares are taken
TRACED = SPAN_PREFIX + "traced"
# one span per program run and its wait (an SVM job, an LM step): the
# last of them ends just after the device's last module, which puts the
# device's clock on the host's
UNITS = (SPAN_PREFIX + "job", SPAN_PREFIX + "step")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_KINDS = (r"all-reduce|all-gather|reduce-scatter|collective-permute"
          r"|all-to-all|collective-broadcast|send|recv")
# an instruction name such as "all-reduce-start.3", or the op kind in
# the instruction's text, "... = f32[8] all-reduce(...)"
COLLECTIVE = re.compile(rf"^%?({_KINDS})|\s({_KINDS})(-start|-done)?\(")

Interval = Tuple[int, int]
Event = Tuple[str, int, int]


def span(name: str):
    """A host span on the profiler's clock: ``with span("step_fn"): ...``.
    Costs next to nothing while no trace is being taken."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def is_collective(op: str) -> bool:
    return COLLECTIVE.search(op) is not None


def short_name(op: str, width: int = 100) -> str:
    """``%fusion.3 = bf16[8,128] fusion(...)`` → ``fusion.3 =
    bf16[8,128] fusion(...`` cut to ``width`` characters."""
    return op.lstrip("%")[:width]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # device plane → leaf operations
    spans: List[Event]                  # the benchmark's host spans

    def to_json(self) -> str:
        return json.dumps({"devices": self.devices, "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)
        return cls({k: [tuple(e) for e in v]
                    for k, v in raw["devices"].items()},
                   [tuple(e) for e in raw["spans"]])


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def leaf_ops(events: Sequence[Event]) -> List[Event]:
    """Drop events that enclose another event of the same line (a loop or
    a call around its body's operations): only leaves are work."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, lo, hi) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < hi and ev[i + 1][2] <= hi \
                and (ev[i + 1][1], ev[i + 1][2]) != (lo, hi):
            continue
        out.append((name, lo, hi))
    return out


def _events(line) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    """Device leaf operations, on the host's clock, and ``bench.`` host
    spans of one trace.

    A device's events come on its own clock, which can stand milliseconds
    off the host's. The profile is stopped right after the last unit span
    (``UNITS``), which ends when the host sees its program finish: the
    device's last module is put to end there."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    ends: Dict[str, int] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = leaf_ops(_events(line))
                elif line.name == MODULES_LINE:
                    ends[plane.name] = max((e[2] for e in _events(line)),
                                           default=0)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIX)]
    if not ops:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} line")
    units = [s[2] for s in spans if s[0] in UNITS]
    devices = {}
    for name, ev in ops.items():
        shift = max(units) - ends[name] if units and ends.get(name) else 0
        devices[name] = [(n, a + shift, b + shift) for n, a, b in ev]
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


# --------------------------------------------------------------- intervals

def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def length(merged: Sequence[Interval]) -> int:
    return sum(b - a for a, b in merged)


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` that the merged ``b`` misses."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return minus([(lo, hi)], busy)


def label(spans: Sequence[Event], lo: int, hi: int) -> str:
    """The innermost benchmark span open at the middle of [lo, hi]."""
    mid = (lo + hi) // 2
    best: Optional[Event] = None
    for s in spans:
        if s[0] != TRACED and s[1] <= mid < s[2]:
            if best is None or s[2] - s[1] < best[2] - best[1]:
                best = s
    return best[0][len(SPAN_PREFIX):] if best else "outside_spans"


# ----------------------------------------------------------------- summary

def window(trace: Trace) -> Interval:
    """The traced stretch: the ``bench.traced`` span."""
    marks = [s for s in trace.spans if s[0] == TRACED]
    if len(marks) != 1:
        raise ValueError(f"expected one {TRACED!r} span, found {len(marks)}")
    return marks[0][1], marks[0][2]


def summarize(trace: Trace, top: int = 10) -> dict:
    """Per-device and mean shares of the traced stretch, in seconds."""
    lo, hi = window(trace)
    per_dev = {}
    op_time: Dict[str, float] = {}
    first_idle: List[Interval] = []
    first = min(trace.devices)
    for name in sorted(trace.devices):
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in trace.devices[name]
               if min(b, hi) > max(a, lo)]
        coll = union([(a, b) for n, a, b in ops if is_collective(n)])
        other = union([(a, b) for n, a, b in ops if not is_collective(n)])
        busy = union(coll + other)
        idle = gaps(busy, lo, hi)
        per_dev[name] = {
            "busy_s": length(busy) * 1e-9,
            "collective_s": length(coll) * 1e-9,
            "exposed_collective_s": length(minus(coll, other)) * 1e-9,
            "other_s": length(other) * 1e-9,
            "idle_s": length(idle) * 1e-9,
        }
        for n, a, b in ops:
            n = short_name(n)
            op_time[n] = op_time.get(n, 0.0) + (b - a) * 1e-9
        if name == first:     # one device's gaps: the host is the same
            first_idle = idle
    n_dev = len(per_dev)
    mean = {k: sum(d[k] for d in per_dev.values()) / n_dev
            for k in next(iter(per_dev.values()))}
    ops_top = sorted(((n, t / n_dev) for n, t in op_time.items()),
                     key=lambda x: -x[1])[:top]
    gaps_top = sorted(first_idle, key=lambda g: g[0] - g[1])[:top]
    return {"window_s": (hi - lo) * 1e-9, "devices": n_dev,
            "per_device": per_dev, "mean": mean,
            "device_ops": [[n, t] for n, t in ops_top],
            "idle_gaps": [[label(trace.spans, a, b), (b - a) * 1e-9]
                          for a, b in gaps_top]}
