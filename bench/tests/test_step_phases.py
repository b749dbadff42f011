"""``bench/step_phases.py``: each step's host phases and the collections
inside it, from wrapped spans."""
import contextlib

from bench import step_phases as sp


@contextlib.contextmanager
def _opened(*args, **kwargs):
    yield


def test_phases_sum_by_name_inside_their_step():
    p = sp.Phases(least_gc_s=0.0)
    step_span, span = p.step_span(_opened), p.span(_opened)
    with span("restore"):                    # outside any step: not kept
        pass
    for n in (4, 5):
        with step_span(n):
            with span("data"):
                pass
            with span("dispatch"):
                p.on_gc("start", {"generation": 2})
                p.on_gc("stop", {"generation": 2})
            with span("fetch"):
                pass
            with span("fetch"):
                pass
    assert [r["step"] for r in p.steps] == [4, 5]
    for row in p.steps:
        assert set(row) == {"step", "step_s", "data", "dispatch", "fetch",
                            "gc"}
        assert row["step_s"] >= row["data"] + row["dispatch"] + row["fetch"]
        assert [g[0] for g in row["gc"]] == [2]


def test_short_collections_and_those_between_steps_are_left_out():
    p = sp.Phases(least_gc_s=60.0)
    with p.step_span(_opened)(0):
        p.on_gc("start", {"generation": 0})
        p.on_gc("stop", {"generation": 0})
    p.on_gc("start", {"generation": 2})
    p.on_gc("stop", {"generation": 2})
    assert "gc" not in p.steps[0]


def test_a_tiny_lm_cell_writes_a_line_a_step(harness, tmp_path, capsys):
    import json
    from repro.runtime import ft
    opened = ft.span, ft.step_span
    out = tmp_path / "phases.jsonl"
    rc = sp.main(["--out", str(out), "--workload", "smollm-360m.s4096",
                  "--seed", str(2 ** 31 + 19), "--seconds", "1",
                  "--trace", "0"])
    assert rc == 0 and (ft.span, ft.step_span) == opened
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(rows) == last["attempted"] + 3    # and the checked steps
    assert all({"data", "dispatch", "fetch"} <= set(r) for r in rows)
