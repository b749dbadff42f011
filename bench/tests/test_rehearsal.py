"""Each cell of ``BENCHMARK.json`` run end to end on the CPU at its tiny
size (``bench/tests/tiny/``), with the harness's look for a chip steered
here (``conftest.harness``): every file is found by name, the timed path
runs, the check passes, the last line has the contract's keys, and each
per-layer reader reads what its cells' traced runs give it."""
import dataclasses
import io
import json
import re
from contextlib import redirect_stdout

import jax
import pytest

from bench import run as R
from bench.tests import conftest as T

KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


def spec():
    return R.load_json(R.ROOT, "BENCHMARK.json")


CELLS = [w["name"] for w in spec()["workloads"]]
# each per-layer metric with each cell that reports it
READS = [(m["name"], cell) for cell in CELLS
         for m in R.cell_metrics(spec(), cell)[1]]


def test_every_entry_has_its_files():
    s = spec()
    assert s["paths"] == ["bench"]
    for c in s["configs"]:
        cfg = R.load_json(R.ROOT, c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        R.load_module("drivers", cfg["driver"])
    for w in s["workloads"]:
        cell = R.load_json(R.BENCH, "workloads", w["name"] + ".json")
        for k in ("config", "traffic", "chips", "why"):
            assert cell[k] == w[k], (w["name"], k)
        assert cell["check"]
        T.tiny(w["name"])
    for c in s["configs"]:
        T.tiny(c["name"])
    for m in s["per_layer"]:
        assert callable(R.load_module("metrics", m["name"]).read)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_file_keeps_its_limits():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= s["run_seconds"] <= 51
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in s["workloads"]}
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in s["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in s["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for w in s["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        reported = [m for m in s["per_layer"] if w["name"] in m["workloads"]]
        assert reported and all(
            w["name"] in next(e for e in s["end_to_end"]
                              if e["name"] == m["moves"]).get(
                                  "workloads", [w["name"]])
            for m in reported)
    assert sum(w["chips"] == 4 for w in s["workloads"]) <= max(
        1, len(s["workloads"]) // 2)


def test_no_tpu_exits_nonzero():
    with pytest.raises(SystemExit) as e:
        R.require_devices(1)
    assert e.value.code not in (0, None)


# the traced run of each cell, and what its per-layer readers were given
TRACED = {}


def run_traced(monkeypatch, cell):
    """The cell's last line with ``--trace 1`` and the ``Reading`` its
    readers got, run once a module."""
    if cell not in TRACED:
        kept, real = [], R.Reading

        class Kept(real):
            def __init__(self, *args):
                super().__init__(*args)
                kept.append(real(*args))
        with monkeypatch.context() as m:
            m.setattr(R, "Reading", Kept)
            TRACED[cell] = (run(cell, 1), kept[-1])
    return TRACED[cell]


def run(cell, trace):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = R.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                     "--seconds", "1", "--trace", str(trace)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end(harness, monkeypatch, cell, trace):
    last = run_traced(monkeypatch, cell)[0] if trace else run(cell, 0)
    assert set(last) == KEYS | ({"breakdown"} if trace else set())
    assert list(last)[-1] == "check"
    assert last["correct"] is True, last
    assert last["attempted"] > 0 and last["failed"] == 0
    e2e, layer = R.cell_metrics(spec(), cell)
    want = [m["name"] for m in (layer if trace else e2e)]
    assert sorted(last["metrics"]) == sorted(want)
    for m in (layer if trace else e2e):
        assert last["metrics"][m["name"]]["unit"] == m["unit"]
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert last["device"]["busy_s"] > 0
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    cellfile = R.load_json(R.BENCH, "workloads", cell + ".json")
    assert set(last["check"]) == set(cellfile["check"])


def test_seed_makes_the_inputs():
    from bench import datagen
    a = datagen.lm_batch(2 ** 31 + 5, 3, 2, 16, 512)
    b = datagen.lm_batch(2 ** 31 + 5, 3, 2, 16, 512)
    c = datagen.lm_batch(2 ** 31 + 6, 3, 2, 16, 512)
    assert (a["tokens"] == b["tokens"]).all()
    assert not (a["tokens"] == c["tokens"]).all()
    k1, k2 = datagen.key(3, 0), datagen.key(3 + 2 ** 32, 0)
    assert not (jax.random.key_data(k1) == jax.random.key_data(k2)).all()


@pytest.mark.parametrize("metric,cell", READS)
def test_every_metric_reader_reads_a_summary(harness, monkeypatch, metric,
                                             cell):
    """Each reader in ``bench/metrics``, given what a traced run of a cell
    it is listed for hands it (the cell's configuration, tiny traffic and
    counts, a summary with the program's scopes), reads a number; it
    reads nothing, and never 0, where its input is absent."""
    _, r = run_traced(monkeypatch, cell)
    assert {"scopes", "host_spans", "unmapped"} <= set(r.summary)
    assert "unmapped" not in r.summary["scopes"]
    read = R.load_module("metrics", metric).read
    value = read(r)
    assert isinstance(value, float) and value >= 0, (metric, value)
    assert read(dataclasses.replace(r, summary=None)) is None
    assert read(dataclasses.replace(r, counts={})) in (None, value)
    bare = {k: v for k, v in r.summary.items()
            if k not in ("scopes", "host_spans", "unmapped")}
    assert read(dataclasses.replace(r, summary=bare)) in (None, value)
