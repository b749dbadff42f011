"""CPU rehearsal helpers: the cells at a tiny size, and the harness with
its look for a chip steered to the CPU."""
import json
import os

import jax
import pytest

from bench import peaks
from bench import run as R
from bench import trace as tr

# one file of tiny sizes per configuration and per cell, by its name
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def tiny(name: str) -> dict:
    """``tiny/<name>.json``: the keys of a configuration's or a cell's
    file that change at the test size (a group of keys merged into the
    file's own group); ``note`` says why."""
    path = os.path.join(TINY, name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no tiny sizes for {name!r}: add {path}")
    with open(path) as f:
        data = json.load(f)
    data.pop("note", None)
    return data


def shrink(path_parts, data):
    """The tiny version of a configuration or cell file."""
    kind, name = path_parts[-2], path_parts[-1][:-len(".json")]
    for k, v in tiny(name).items():
        data[k] = {**data[k], **v} if isinstance(v, dict) else v
    if kind == "workloads":
        data["chips"] = 1                  # one CPU device here
    return data


def cells_driven_by(driver: str, chips=(1, 4)) -> list:
    """The cells of ``BENCHMARK.json`` whose configuration ``driver``
    runs, on the given numbers of chips."""
    spec = R.load_json(R.ROOT, "BENCHMARK.json")
    return [w["name"] for w in spec["workloads"] if w["chips"] in chips
            and R.load_json(R.BENCH, "configs", w["config"] + ".json")[
                "driver"] == driver]


def cpu_trace(path):
    """The CPU runs XLA's operations on host threads: read those as the
    one device's operations, so that the reduction has something to
    read."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if e.name.startswith(tr.SPAN_PREFIX):
                    spans.append((e.name, lo, hi))
                elif any(k == "hlo_op" for k, _ in e.stats):
                    ops.append((e.name, lo, hi))
    return tr.Trace({"/device:CPU:0": tr.leaf_ops(ops)}, sorted(spans))


@pytest.fixture
def harness(monkeypatch):
    real = R.load_json
    monkeypatch.setattr(R, "load_json", lambda *p: shrink(p, real(*p))
                        if p[-2] in ("configs", "workloads") else real(*p))
    monkeypatch.setattr(R, "require_devices",
                        lambda chips: jax.devices()[:1])
    import repro.launch.cache
    monkeypatch.setattr(repro.launch.cache, "use_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(tr, "load", cpu_trace)
    # a table of this host's peaks does not exist; any kind's will do to
    # exercise the readers
    real_peaks = peaks.lookup
    monkeypatch.setattr(peaks, "lookup",
                        lambda kind: real_peaks("TPU v5 lite"))
    return R
