"""The reduction by the program's names (``bench.scopes``): the op_name
rule, the arithmetic on hand-made events, and each cell's program traced
on the CPU at a tiny size."""
import io
import json
import os
from contextlib import redirect_stdout

import pytest

from bench import scope_report, scopes as sc, trace as tr
from repro.core import telemetry

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_prefix_is_the_programs():
    assert sc.PREFIX == telemetry.PREFIX


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/repro.lm.optimizer/pow", "lm.optimizer"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/repro.lm.attention/dot_general", "lm.attention"),
    ("jit(step)/jvp(repro.lm.loss)/bcd,vd->bcv/dot_general", "lm.loss"),
    ("jit(f)/repro.lm.sync/repro.svm.sync/psum", "svm.sync"),
    ("jit(step)/jvp(bsv,vd->bsd)/dot_general", ""),
    ("jit(f)/not_repro.lm.mlp/add", ""),
])
def test_scope_is_a_path_component(op_name, scope):
    assert sc.scope_of(op_name) == scope


HLO = """HloModule jit_worker, is_scheduled=true

%fused (p: f32[4]) -> f32[4] {
  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(worker)/repro.svm.block/mul"}
}

ENTRY %main (w: f32[4]) -> f32[4] {
  %w = f32[4]{0} parameter(0), metadata={op_name="w"}
  %fusion.5 = f32[4]{0} fusion(%w), kind=kLoop, calls=%fused, metadata={op_name="jit(worker)/repro.svm.block/mul" stack_frame_id=3}
  %psum.9 = f32[4]{0} all-reduce-start(%fusion.5), channel_id=1, to_apply=%add, metadata={op_name="jit(worker)/shard_map/repro.svm.sync/psum"}
  ROOT %copy.2 = f32[4]{0} copy(%psum.9)
}
"""


def test_op_table_reads_kinds_and_scopes():
    t = sc.op_table([HLO])["jit_worker"]
    assert t["fusion.5"] == ("fusion", "svm.block")
    assert t["psum.9"] == ("all-reduce-start", "svm.sync")
    assert t["copy.2"] == ("copy", "")
    assert t["w"] == ("parameter", "")
    # the same module compiled twice with other op_names: no telling
    other = HLO.replace("repro.svm.block/mul\" stack", "repro.lm.mlp/mul\" stack")
    again = sc.op_table([HLO, other])["jit_worker"]
    assert again["fusion.5"] == ("fusion", None)
    assert again["psum.9"] == ("all-reduce-start", "svm.sync")


@pytest.mark.parametrize("kind,starts", [
    ("all-reduce", True), ("all-reduce-start", True), ("send", True),
    ("collective-permute-start", True), ("all-reduce-done", False),
    ("fusion", False), ("copy-start", False)])
def test_an_exchange_is_bench_traces_collective(kind, starts):
    assert sc.exchange(kind) is starts


@pytest.mark.parametrize("name,stats,run,want", [
    # a TPU's XLA Ops event: the instruction's text, inside its module run
    ("%fusion.16 = f32[256,256]{1,0:T(8,128)S(1)} fusion(f32[256,256]{1,0:"
     "T(8,128)S(1)} %copy.11), kind=kOutput, calls=%fused_computation.3",
     {}, "jit_worker(12)", ("jit_worker", "fusion.16")),
    ("%psum.9 = f32[2000]{0:T(1024)S(1)} all-reduce(f32[2000]{0:T(1024)"
     "S(1)} %fusion.6), channel_id=1", {"program_id": 12},
     "jit_worker(12)", ("jit_worker", "psum.9")),
    # the CPU's: the stats name both
    ("wrapped_sine", {"hlo_op": "wrapped_sine", "hlo_module": "jit_f",
                      "program_id": 5}, "", ("jit_f", "wrapped_sine")),
])
def test_resolve_module_and_instruction(name, stats, run, want):
    assert sc.resolve(name, stats, run) == want


def xspace(devices, spans):
    """A profile as a TPU writes one, in the protobuf's text form:
    ``devices`` maps a device plane to its module runs and operations
    (name, start, end in ns on its own clock), ``spans`` are host
    events."""
    def plane(pid, name, lines):
        names, out = {}, [f'planes {{ id: {pid} name: "{name}"']
        for lid, (line, events) in enumerate(lines, 1):
            evs = " ".join(
                f"events {{ metadata_id: {names.setdefault(n, len(names) + 1)}"
                f" offset_ps: {lo * 1000} duration_ps: {(hi - lo) * 1000} }}"
                for n, lo, hi in events)
            out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                       f'{evs} }}')
        out += [f"event_metadata {{ key: {i} value {{ id: {i} "
                f"name: {json.dumps(n)} }} }}" for n, i in names.items()]
        return "\n".join(out) + "\n}"
    planes = [plane(i, name, [(tr.MODULES_LINE, runs), (tr.OPS_LINE, ops)])
              for i, (name, (runs, ops)) in enumerate(sorted(devices.items()),
                                                       1)]
    planes.append(plane(len(planes) + 1, "/host:CPU", [("python3", spans)]))
    return "\n".join(planes)


def test_tpu_profile_read_by_scope(tmp_path):
    """A TPU's events name neither op_name nor module: each is found
    from the op's text and its enclosing module run, on the clock and
    with the operations ``bench.trace.load`` gives."""
    ops = [("%copy.2 = f32[4]{0} copy(f32[4]{0} %psum.9)", 1000, 1050),
           ("%fusion.5 = f32[4]{0} fusion(f32[4]{0} %w), kind=kLoop", 1100,
            1300),
           ("%psum.9 = f32[4]{0} all-reduce(f32[4]{0} %fusion.5)", 1300,
            1400),
           # the same instruction name in a program whose text is not given
           ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p)", 2100, 2150)]
    runs = [("jit_worker(7)", 1000, 2000), ("jit_other(8)", 2100, 2200)]
    late = lambda evs: [(n, a + 500, b + 500) for n, a, b in evs]
    spans = [("bench.traced", 0, 3000), ("bench.job", 900, 2250),
             ("repro.step", 900, 2250), ("repro.dispatch", 950, 1000)]
    path = tmp_path / "t.xplane.pb"
    from jax.profiler import ProfileData
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace(
        {"/device:TPU:0": (runs, ops),
         "/device:TPU:1": (late(runs), late(ops))}, spans)))
    hlo = HLO.replace("all-reduce-start(", "all-reduce(")
    plain = tr.load(str(path))
    t = sc.scoped(plain, str(path), [hlo])
    assert t.plain() == plain
    # each device's last module run is put to end with the last bench.job:
    # the first device's clock moves by 50 ns, the second's by -450 ns
    for dev in ("/device:TPU:0", "/device:TPU:1"):
        assert [op[1:] for op in t.devices[dev]] == [
            (1050, 1100, "copy", "unscoped"),
            (1150, 1350, "fusion", "svm.block"),
            (1350, 1450, "all-reduce", "svm.sync"),
            (2150, 2200, "", "unmapped")]
        assert t.modules[dev] == [("jit_worker", 1050, 2050),
                                  ("jit_other", 2150, 2250)]
    assert [s[0] for s in t.spans] == ["bench.traced", "bench.job",
                                       "repro.step", "repro.dispatch"]
    assert sc.outside_units(t, [(900, 2250)]) == 0.0
    s = sc.summarize(t)
    assert s["collectives"] == {"svm.sync": 1.0}
    assert s["scopes"]["unmapped"] == pytest.approx(50e-9)


def synthetic():
    """Two devices over [0, 1000) ns and one step's host spans: data
    [0, 100), dispatch [100, 300) with the benchmark's step_fn inside it,
    fetch [300, 900) and a save [900, 950)."""
    ops = {
        "/device:TPU:0": [("fusion.1", 150, 400, "fusion", "lm.attention"),
                          ("fusion.2", 350, 500, "fusion", "lm.mlp"),
                          ("psum.1", 500, 600, "all-reduce", "lm.sync"),
                          ("copy.1", 800, 850, "copy", "unscoped")],
            "/device:TPU:1": [("fusion.1", 150, 350, "fusion", "lm.attention"),
                              ("fusion.9", 700, 800, "fusion", "unmapped")],
    }
    spans = [("bench.traced", 0, 1000), ("repro.step", 0, 960),
             ("repro.data", 0, 100), ("bench.data", 10, 90),
             ("repro.dispatch", 100, 300), ("bench.step_fn", 110, 290),
             ("repro.fetch", 300, 900), ("repro.save", 900, 950)]
    return sc.Scoped(ops, {"/device:TPU:0": [("jit_step", 150, 850)],
                           "/device:TPU:1": [("jit_step", 150, 800)]},
                     spans)


def test_summary_by_scope_and_span():
    s = sc.summarize(synthetic())
    assert s["devices"] == 2 and s["window_s"] == pytest.approx(1000e-9)
    # attention: [150, 400) on one chip, [150, 350) on the other
    assert s["scopes"]["lm.attention"] == pytest.approx(225e-9)
    assert s["scopes"]["lm.mlp"] == pytest.approx(75e-9)
    assert s["scopes"]["unscoped"] == pytest.approx(25e-9)
    assert s["scopes"]["unmapped"] == pytest.approx(50e-9)
    assert s["scope_ops"]["lm.attention"] == 1.0
    assert s["collectives"] == {"lm.sync": 0.5}
    h = s["host_spans"]
    assert h["step"] == pytest.approx(10e-9)   # 960 − 100 − 200 − 600 − 50
    assert h["dispatch"] == pytest.approx(200e-9)  # bench spans are not kids
    assert h["fetch"] == pytest.approx(600e-9)
    assert h["save"] == pytest.approx(50e-9)
    # device 0 idles [600, 800) in fetch, then [0, 150) and [850, 1000),
    # labelled at their middles, 75 in data and 925 in save
    assert s["idle_gaps"][0] == ["fetch", pytest.approx(200e-9)]
    assert [g[0] for g in s["idle_gaps"]] == ["fetch", "data", "save"]


def test_plain_trace_is_what_bench_trace_reads():
    t = synthetic()
    p = t.plain()
    assert p.devices["/device:TPU:1"] == [("fusion.1", 150, 350),
                                          ("fusion.9", 700, 800)]
    assert {s[0] for s in p.spans} == {"bench.traced", "bench.data",
                                      "bench.step_fn"}
    assert tr.summarize(p)["mean"]["busy_s"] > 0


def test_module_runs_inside_their_host_intervals():
    t = synthetic()
    iv = sc.dispatch_to_fetch(t.spans)
    assert iv == [(100, 900)]
    assert sc.outside_units(t, iv) == 0.0
    t.modules["/device:TPU:0"] = [("jit_step", 50, 850)]
    assert sc.outside_units(t, iv) == pytest.approx(50 / 800)


def test_recording_round_trips():
    t = synthetic()
    assert sc.Scoped.from_json(t.to_json()) == t


def test_bench_trace_summary_of_the_recorded_scan_unchanged():
    """``bench/trace.py``'s reading of its recording, key for key and
    number for number, as the accepted metrics read it."""
    with open(os.path.join(DATA, "scan_1chip.trace.json")) as f:
        got = tr.summarize(tr.Trace.from_json(f.read()))
    with open(os.path.join(DATA, "scan_1chip.summary.json")) as f:
        assert json.dumps(got, sort_keys=True) == f.read().strip()


@pytest.mark.parametrize("cell", ["svm-epsilon.k4.b64", "smollm-360m.s4096"])
def test_cell_traced_by_scope_on_the_cpu(harness, cell, tmp_path):
    """The chip's path at a tiny size: the cell run as ``bench/run.py``
    runs it, its trace read by scope, its recording trimmed and read
    back."""
    out = io.StringIO()
    rec = str(tmp_path / "rec.json")
    with redirect_stdout(out):
        assert scope_report.main(["--workload", cell, "--seed",
                                  str(2 ** 31 + 17), "--seconds", "1",
                                  "--record", rec]) == 0
    r = json.loads(out.getvalue().strip().splitlines()[-1])
    assert r["result"]["correct"] is True
    assert set(r["result"]["breakdown"]) == {"device_ops", "idle_gaps"}
    s = r["summary"]
    assert "unmapped" not in s["scopes"]      # every op's program is known
    if cell.startswith("svm"):
        assert s["scopes"]["svm.block"] > 0 and s["scopes"]["svm.sync"] > 0
        # every executed exchange is the block's
        assert s["collectives"] == {
            "svm.sync": r["units"] * r["per_unit"]["syncs"]}
        assert r["result"]["metrics"]["svm_sync_us"]["value"] > 0
    else:
        for name in ("lm.attention", "lm.mlp", "lm.loss", "lm.optimizer"):
            assert s["scopes"][name] > 0, name
        for name in ("step", "data", "dispatch", "fetch"):
            assert s["host_spans"][name] > 0, name
        assert r["result"]["metrics"]["dispatch_ms.lm"]["value"] > 0
    with open(rec) as f:
        t = sc.Scoped.from_json(f.read())
    assert sc.summarize(t)["devices"] == 1


# the recorded scan's one program, with a scope on two of its
# instructions; copy-start and copy-done are left out of the text
SCAN_HLO = """HloModule jit_scan, is_scheduled=true

ENTRY %main (xs.1: f32[8,256,256]) -> f32[256,256] {
  %convert.1 = bf16[8,256,256]{2,1,0} convert(%xs.1)
  %copy.11 = f32[256,256]{1,0} copy(%get-tuple-element.36), metadata={op_name="jit(scan)/while/body/repro.COPY/copy"}
  ROOT %fusion.16 = f32[256,256]{1,0} fusion(%copy.11, %get-tuple-element.41), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(scan)/while/body/repro.FUSION/dot_general"}
}
"""
# each instruction's device ns in the recorded traced stretch
SCAN_NS = {"fusion.16": 27970, "copy.11": 722, "convert.1": 6691,
           "copy-start": 11, "copy-start.1": 6, "copy-done": 1151,
           "copy-done.1": 276}


def recorded_scan(fusion_scope: str, copy_scope: str) -> sc.Scoped:
    """The recorded scan by scope, each ``bench.job`` a ``repro.step``
    whose first quarter is its ``repro.dispatch``."""
    with open(os.path.join(DATA, "scan_1chip.trace.json")) as f:
        t = tr.Trace.from_json(f.read())
    table = sc.op_table([SCAN_HLO.replace("COPY", copy_scope)
                         .replace("FUSION", fusion_scope)])
    ops = {d: [(n, a, b) + sc.labels(table, *sc.resolve(n, {}, "jit_scan(3)"))
               for n, a, b in ev] for d, ev in t.devices.items()}
    steps = [(a, b) for n, a, b in t.spans if n == "bench.job"]
    spans = t.spans + [s for a, b in steps for s in (
        ("repro.step", a, b), ("repro.dispatch", a, a + (b - a) // 4))]
    return sc.Scoped(ops, {}, sorted(spans, key=lambda s: s[1]))


def test_layer_summary_of_the_recorded_scan():
    """The scope keys on a trace recorded on the chip: seconds by scope
    and unmapped add up to the busy time, and the summary's other keys
    are ``bench/trace.py``'s, unchanged."""
    t = recorded_scan("svm.block", "svm.sync")
    s = tr.summarize(t.plain())
    with open(os.path.join(DATA, "scan_1chip.summary.json")) as f:
        assert json.dumps(s, sort_keys=True) == f.read().strip()
    keys = sc.layer_summary(t)
    assert keys["scopes"] == pytest.approx({
        "svm.block": SCAN_NS["fusion.16"] * 1e-9,
        "svm.sync": SCAN_NS["copy.11"] * 1e-9,
        "unscoped": SCAN_NS["convert.1"] * 1e-9})
    assert keys["unmapped"] == pytest.approx(sum(
        SCAN_NS[k] for k in SCAN_NS if k.startswith("copy-")) * 1e-9)
    assert sum(keys["scopes"].values()) + keys["unmapped"] == \
        pytest.approx(s["mean"]["busy_s"])
    jobs = [b - a for n, a, b in t.spans if n == "bench.job"]
    assert keys["host_spans"]["dispatch"] == pytest.approx(
        sum(j // 4 for j in jobs) * 1e-9)
    assert keys["host_spans"]["step"] == pytest.approx(
        sum(j - j // 4 for j in jobs) * 1e-9)


# a reading of the recorded scan: two units, each of 8 blocks and syncs
SCAN_COUNTS = {"traced_samples": 2048, "traced_jobs": 2, "traced_blocks": 16,
               "traced_syncs": 16, "traced_steps": 2, "traced_tokens": 2}


def scan_reading(metric: str, t: sc.Scoped):
    from bench import peaks, run as R
    lm = metric.endswith(".lm")
    config = R.load_json(R.BENCH, "configs",
                         "smollm-360m.json" if lm else "svm-epsilon.json")
    cell = R.load_json(R.BENCH, "workloads", "smollm-360m.s4096.json" if lm
                       else "svm-epsilon.k4.b64.json")
    summary = tr.summarize(t.plain())
    summary.update(sc.layer_summary(t))
    return R.load_module("metrics", metric).read, R.Reading(
        summary, SCAN_COUNTS, peaks.lookup("TPU v5 lite"), 1, config, cell)


@pytest.mark.parametrize("metric,fusion,copy,want", [
    ("svm_block_us", "svm.block", "svm.sync", 1e6 * 27970e-9 / 16),
    ("svm_sync_us", "svm.block", "svm.sync", 1e6 * 722e-9 / 16),
    ("attn_ms.lm", "lm.attention", "lm.optimizer", 1e3 * 27970e-9 / 2),
    ("opt_ms.lm", "lm.attention", "lm.optimizer", 1e3 * 722e-9 / 2),
    ("dispatch_ms.lm", "lm.attention", "lm.optimizer", None),
])
def test_scope_readers_on_the_recorded_scan(metric, fusion, copy, want):
    """Each reader of a scope or span on the recorded scan; with the
    scope or span absent, it reads nothing, not 0."""
    t = recorded_scan(fusion, copy)
    if want is None:
        jobs = [b - a for n, a, b in t.spans if n == "bench.job"]
        want = 1e3 * sum(j // 4 for j in jobs) * 1e-9 / 2
    read, r = scan_reading(metric, t)
    assert read(r) == pytest.approx(want)
    read, r = scan_reading(metric, recorded_scan("lm.mlp", "lm.loss"))
    r.summary["host_spans"].pop("dispatch")
    assert read(r) is None


# what the readers of bench/trace.py's summary read on the recorded scan
# with SCAN_COUNTS, before scopes were added to the summary
EXISTING = {
    "host_gap_ms.lm": 0.6788265000000001,
    "idle_share.lm": 97.35908725833286,
    "idle_share.svm": 97.35908725833286,
    "mfu.lm": 80.64947492866563,
    "mfu.svm": 1.4352939050317286,
    "svm_block_roofline": 54.3484032011471,
    "sync_exposed_share.svm": 0.0,
}


@pytest.mark.parametrize("metric", sorted(EXISTING))
def test_existing_readers_unchanged_on_the_recorded_scan(metric):
    read, r = scan_reading(metric, recorded_scan("svm.block", "svm.sync"))
    assert read(r) == EXISTING[metric]
