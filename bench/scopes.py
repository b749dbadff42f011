"""Attribute a profiler trace to the names the program gives its work.

``bench/trace.py`` sorts device time into collective and other work and
labels idle gaps with the benchmark's own spans. This module reads the
same trace, as ``bench.trace.load`` loads it, through the program's names
(``repro.core.telemetry``):

* Device time by scope. An operation's scope is the last ``repro.``
  component of its HLO ``op_name``, read as a component of the path,
  never as a prefix: the path is split at ``/``, ``(`` and ``)``, because
  transforms wrap the path (``jit(step)/transpose(jvp())/checkpoint/
  rematted_computation/repro.lm.attention/dot_general``) or the scope
  itself (``jit(step)/jvp(repro.lm.loss)/...``). A fusion carries its
  root's op_name, so it counts whole to its root's scope. An operation
  with no ``repro.`` component is ``unscoped``; one whose program text was
  not given, or is given twice with different op_names, is ``unmapped``.
* Host time by span: each ``repro.`` span's self time (its length less
  what the ``repro.`` spans inside it cover), and the idle gaps of the
  first device, each labelled with the innermost ``repro.`` span open at
  its middle (``bench.trace.label`` over the program's spans).

Where the op_names come from. The profiler's device events name an
instruction and its program (module), not the op_name, so the op_names
are read from the compiled programs' text (``metadata={op_name="..."}``),
keyed by module and instruction, so that ``fusion.5`` of two programs do
not collide. :func:`resolve` finds module and instruction for every event,
on either platform: the ``hlo_module`` and ``hlo_op`` stats where the
profiler gives them (the CPU), else the module run enclosing the event on
its device's ``XLA Modules`` line and the ``%name =`` head of the event's
name (a TPU).

The clock and the operations are ``bench.trace.load``'s: :func:`scoped`
takes its trace and only adds, to each of its operations, the kind and
scope of the instruction behind it, checking that the profile holds the
same operations in the same order; each device's module runs are moved
onto the host's clock by the shift ``load`` gave that device's operations.
A collective is what ``bench.trace.is_collective`` calls one.

``bench/run.py`` adds :func:`layer_summary` of every traced run, read
from the program texts its driver hands over, to the summary that the
per-layer readers get.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace as tr

# the program's prefix (``repro.core.telemetry.PREFIX``), kept here so
# that the reduction reads a program without any scope as all unscoped
PREFIX = "repro."
UNSCOPED, UNMAPPED = "unscoped", "unmapped"
# the one CPU device: the CPU runs XLA's operations on host threads
CPU_DEVICE = "/device:CPU:0"
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^{}]*op_name="([^"]*)"')
_HEAD = re.compile(r"^%?([^\s=]+)")
_RUN_ID = re.compile(r"\(\d+\)$")
# a multi-line ``{…}}`` attribute block, as a Mosaic kernel's
# ``kernel_metadata`` runs over three lines ahead of its op_name
_BLOCK = re.compile(r"\{\n([^\n]*)\n\}\}")

Event = tr.Event
# an operation: its name as ``bench.trace.load`` gives it, start and end
# on the host's clock (ns), the instruction's kind (``all-reduce``,
# ``fusion``, ...) and its scope
Op = Tuple[str, int, int, str, str]


def scope_of(op_name: str) -> str:
    """The last ``repro.`` component of an op_name, without the prefix;
    ``""`` where there is none."""
    found = [c for c in re.split(r"[/()]", op_name) if c.startswith(PREFIX)]
    return found[-1][len(PREFIX):] if found else ""


def joined(text: str) -> str:
    """``text`` with each multi-line ``{…}}`` attribute block on the line
    of the instruction it belongs to."""
    return _BLOCK.sub(r"{\1}}", text)


def op_table(texts: Iterable[str]
             ) -> Dict[str, Dict[str, Tuple[str, Optional[str]]]]:
    """Module → instruction → (kind, scope), from compiled HLO text, each
    instruction read from its line once multi-line attribute blocks are
    :func:`joined` onto it (so a Mosaic kernel, such as the splash
    attention kernels, reads its scope). Where two texts name the same
    module and disagree on an instruction, its scope is ``None``: it
    cannot be told which of them ran."""
    table: Dict[str, Dict[str, Tuple[str, Optional[str]]]] = {}
    for text in map(joined, texts):
        m = _MODULE.search(text)
        if m is None:
            raise ValueError("HLO text without an 'HloModule' line")
        ins = table.setdefault(m.group(1), {})
        for line in text.splitlines():
            i = _INSTR.match(line)
            if i is None:
                continue
            o = _OP_NAME.search(line)
            got = (i.group(2), scope_of(o.group(1)) if o else "")
            if ins.setdefault(i.group(1), got) != got:
                ins[i.group(1)] = (got[0], None)
    return table


def exchange(kind: str) -> bool:
    """An operation of this kind starts an exchange between chips: a
    collective by ``bench.trace.is_collective``, each counted once (an
    asynchronous one by its start, not its ``-done``)."""
    return tr.is_collective(f" {kind}(") and not kind.endswith("-done")


def resolve(name: str, stats: dict, run: str) -> Tuple[str, str]:
    """(module, instruction) of one device event: the ``hlo_module`` and
    ``hlo_op`` stats where present, else ``run``, the name of the module
    run that encloses the event (``jit_step(12)`` → ``jit_step``), and the
    head of the event's name (``%fusion.16 = f32[...] ...`` →
    ``fusion.16``)."""
    head = _HEAD.match(name)
    instr = stats.get("hlo_op") or (head.group(1) if head else name)
    module = stats.get("hlo_module") or _RUN_ID.sub("", run)
    return str(module), str(instr)


@dataclasses.dataclass
class Scoped:
    devices: Dict[str, List[Op]]        # device → leaf operations
    modules: Dict[str, List[Event]]     # device → its module runs
    spans: List[Event]                  # ``bench.`` and ``repro.`` spans

    def to_json(self) -> str:
        return json.dumps({"devices": self.devices, "modules": self.modules,
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Scoped":
        raw = json.loads(text)
        return cls({k: [tuple(e) for e in v]
                    for k, v in raw["devices"].items()},
                   {k: [tuple(e) for e in v]
                    for k, v in raw["modules"].items()},
                   [tuple(e) for e in raw["spans"]])

    def plain(self) -> tr.Trace:
        """The trace as ``bench.trace`` reads it: operations by name,
        the benchmark's spans alone."""
        return tr.Trace({d: [op[:3] for op in ops]
                         for d, ops in self.devices.items()},
                        [s for s in self.spans
                         if s[0].startswith(tr.SPAN_PREFIX)])


def _inside(runs: Sequence[Event], t: int) -> str:
    for name, lo, hi in runs:
        if lo <= t < hi:
            return name
    return ""


def _profile(path: str):
    """Each device's operations as (name, start, end, module,
    instruction) on the device's own clock, in ``bench.trace.load``'s
    order; each device's module runs; the ``repro.`` host spans."""
    from jax.profiler import ProfileData
    found: Dict[str, List[Tuple[str, int, int, dict, str]]] = {}
    runs: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name if plane.name.startswith("/device:") else None
        for line in plane.lines:
            if device is not None and line.name == tr.MODULES_LINE:
                runs[device] = [(e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events]
        for line in plane.lines:
            for e in line.events:
                lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if device is None and e.name.startswith(PREFIX):
                    spans.append((e.name, lo, hi))
                    continue
                stats = {k: v for k, v in e.stats}
                if device is not None and line.name != tr.OPS_LINE:
                    continue
                if device is None and "hlo_op" not in stats:
                    continue
                where = device or CPU_DEVICE
                found.setdefault(where, []).append(
                    (e.name, lo, hi, stats,
                     _inside(runs.get(where, []), lo)))
    ops = {}
    for dev, evs in found.items():
        keep = tr.leaf_ops([(str(i), lo, hi)
                            for i, (_, lo, hi, _, _) in enumerate(evs)])
        ops[dev] = [(n, lo, hi) + resolve(n, st, run)
                    for n, lo, hi, st, run in (evs[int(k)] for k, _, _ in
                                               keep)]
    return ops, runs, spans


def labels(table: dict, module: str, instr: str) -> Tuple[str, str]:
    """(kind, scope) of one instruction by :func:`op_table`'s ``table``:
    ``unscoped`` where its op_name has no ``repro.`` component,
    ``unmapped`` where its program's text was not given."""
    kind, scope = table.get(module, {}).get(instr, ("", None))
    return kind, UNMAPPED if scope is None else scope or UNSCOPED


def scoped(plain: tr.Trace, path: str, texts: Iterable[str]) -> Scoped:
    """``plain``, the trace ``bench.trace.load`` made of ``path``, with
    each operation's kind and scope, each device's module runs and the
    program's host spans; ``texts`` are the compiled HLO texts of the
    programs that ran in it."""
    table = op_table(texts)
    ops, runs, spans = _profile(path)
    devices, modules = {}, {}
    for dev, mine in plain.devices.items():
        raw = ops.get(dev, [])
        if [op[0] for op in raw] != [op[0] for op in mine]:
            raise ValueError(f"{path}: the profile's operations on {dev} "
                             "are not those bench.trace.load read")
        shifts = {a - r[1] for (_, a, _), r in zip(mine, raw)}
        if len(shifts) > 1:
            raise ValueError(f"{path}: bench.trace.load moved {dev}'s "
                             "operations by more than one shift")
        shift = shifts.pop() if shifts else 0
        devices[dev] = [(name, lo, hi) + labels(table, module, instr)
                        for (name, lo, hi), (_, _, _, module, instr)
                        in zip(mine, raw)]
        modules[dev] = [(_RUN_ID.sub("", n), lo + shift, hi + shift)
                        for n, lo, hi in runs.get(dev, [])]
    return Scoped(devices, modules,
                  sorted(plain.spans + spans, key=lambda s: s[1]))


# ----------------------------------------------------------------- summary

def self_times(spans: Sequence[Event], lo: int, hi: int) -> Dict[str, float]:
    """Seconds of each ``repro.`` span name inside [lo, hi) that no other
    ``repro.`` span nested in it covers."""
    own = [s for s in spans if s[0].startswith(PREFIX)]
    out: Dict[str, float] = {}
    for i, (name, a, b) in enumerate(own):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        kids = tr.union([(max(x, a), min(y, b)) for j, (_, x, y)
                         in enumerate(own) if j != i and a <= x and y <= b
                         and (x, y) != (a, b)])
        key = name[len(PREFIX):]
        out[key] = out.get(key, 0.0) + (b - a - tr.length(kids)) * 1e-9
    return out


def label(spans: Sequence[Event], lo: int, hi: int) -> str:
    """The innermost ``repro.`` span open at the middle of [lo, hi), as
    ``bench.trace.label`` finds the innermost benchmark span."""
    return tr.label([(tr.SPAN_PREFIX + n[len(PREFIX):], a, b)
                     for n, a, b in spans if n.startswith(PREFIX)], lo, hi)


def summarize(t: Scoped, top: int = 10) -> dict:
    """Seconds of device time by scope, op counts and exchanges by scope
    (means of chips), self seconds by host span, and the longest idle gaps
    by span, over the traced stretch (the ``bench.traced`` span)."""
    lo, hi = tr.window(t.plain())
    secs: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    colls: Dict[str, int] = {}
    first = min(t.devices)
    gaps: List[tr.Interval] = []
    for dev, ops in sorted(t.devices.items()):
        ops = [(n, max(a, lo), min(b, hi), k, s) for n, a, b, k, s in ops
               if min(b, hi) > max(a, lo)]
        by_scope: Dict[str, List[tr.Interval]] = {}
        for n, a, b, k, s in ops:
            by_scope.setdefault(s, []).append((a, b))
            counts[s] = counts.get(s, 0) + 1
            if exchange(k):
                colls[s] = colls.get(s, 0) + 1
        for s, iv in by_scope.items():
            secs[s] = secs.get(s, 0.0) + tr.length(tr.union(iv)) * 1e-9
        if dev == first:
            gaps = tr.gaps(tr.union([(a, b) for _, a, b, _, _ in ops]),
                           lo, hi)
    n = len(t.devices)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"window_s": (hi - lo) * 1e-9, "devices": n,
            "scopes": {s: v / n for s, v in sorted(secs.items())},
            "scope_ops": {s: c / n for s, c in sorted(counts.items())},
            "collectives": {s: c / n for s, c in sorted(colls.items())},
            "host_spans": self_times(t.spans, lo, hi),
            "idle_gaps": [[label(t.spans, a, b), (b - a) * 1e-9]
                          for a, b in longest]}


def layer_summary(t: Scoped) -> dict:
    """What the harness adds to ``bench.trace.summarize``'s summary for
    the per-layer readers: ``scopes``, device seconds by scope (``unscoped``
    for operations under none), mean over chips; ``host_spans``, self
    seconds of each ``repro.`` span; ``unmapped``, device seconds of
    operations whose program's text was not given, or was given twice
    with different op_names (mean over chips)."""
    s = summarize(t)
    scopes = dict(s["scopes"])
    unmapped = scopes.pop(UNMAPPED, 0.0)
    return {"scopes": scopes, "host_spans": s["host_spans"],
            "unmapped": unmapped}


def dispatch_to_fetch(spans: Sequence[Event]) -> List[tr.Interval]:
    """For each ``repro.step``: from its ``dispatch`` span's start to its
    ``fetch`` span's end, where its program runs on the device."""
    out = []
    for name, lo, hi in spans:
        if name != PREFIX + "step":
            continue
        inner = {n: (a, b) for n, a, b in spans if lo <= a and b <= hi}
        d, f = inner.get(PREFIX + "dispatch"), inner.get(PREFIX + "fetch")
        if d and f:
            out.append((d[0], f[1]))
    return out


def outside_units(t: Scoped, units: Sequence[tr.Interval]) -> float:
    """The largest share of a module run that lies outside the unit
    interval it overlaps most (0 where every run lies inside one): how
    far the device's clock, once moved, disagrees with the host's."""
    worst = 0.0
    for runs in t.modules.values():
        for _, a, b in runs:
            best = max(units, key=lambda u: min(u[1], b) - max(u[0], a))
            out = max(0, best[0] - a) + max(0, b - best[1])
            worst = max(worst, out / max(1, b - a))
    return worst
