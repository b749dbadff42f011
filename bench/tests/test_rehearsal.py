"""Each cell run end to end on the CPU at a tiny size, with the harness's
look for a chip steered here (``conftest.harness``): every file is found
by name, the timed path runs, the check passes, and the last line has the
contract's keys."""
import io
import json
import re
from contextlib import redirect_stdout

import jax
import pytest

from bench import run as R

KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


def spec():
    return R.load_json(R.ROOT, "BENCHMARK.json")


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


@pytest.mark.parametrize("cell", ["smollm-360m.s4096", "svm-epsilon.k4.b64"])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end(harness, cell, trace):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = R.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                     "--seconds", "1", "--trace", str(trace)])
    assert rc == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
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


def test_every_metric_reader_reads_a_summary():
    """Each reader in ``bench/metrics`` turns a trace summary and a run's
    counts into a number, or into nothing where it has nothing to read."""
    import glob
    import os
    from bench import peaks
    from bench.tests.test_trace import synthetic
    from bench import trace as tr
    summary = tr.summarize(synthetic())
    cfg = {"features": 2000, "config": R.load_json(
        R.BENCH, "configs", "smollm-360m.json")["config"]}
    cell = {"traffic_params": {"seq_len": 4096}}
    counts = {"traced_samples": 1000, "traced_tokens": 10,
              "traced_steps": 1}
    for path in sorted(glob.glob(os.path.join(R.BENCH, "metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        read = R.load_module("metrics", name).read
        r = R.Reading(summary, counts, peaks.lookup("TPU v5 lite"), 2, cfg,
                      cell)
        value = read(r)
        assert isinstance(value, float) and value >= 0, (name, value)
        empty = R.Reading(None, {}, peaks.lookup("TPU v5 lite"), 2, cfg,
                          cell)
        assert read(empty) is None, name
