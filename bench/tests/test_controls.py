"""Each cell's control, at a size a test run holds: the reference one
precision step below what the configuration states, put in the program's
place, fails the cell's limits, and the program passes them. On the chip
the same readings come from ``bench/control.py`` at the cell's own size.
"""
import jax
import pytest

from bench import run as R
from bench.tests import conftest as T


def tiny_ctx(cell_name, seed):
    cell = T.shrink(("workloads", cell_name + ".json"),
                    R.load_json(R.BENCH, "workloads", cell_name + ".json"))
    config = T.shrink(("configs", cell["config"] + ".json"),
                      R.load_json(R.BENCH, "configs",
                                  cell["config"] + ".json"))
    return R.Context(cell_name, cell, config, seed, 0.0, False,
                     jax.devices()[:1], 0.0, lambda **kw: None)


def fails(readings, limits):
    """Over the limits of the numbers read; the tokens fed to the
    program's steps are no reading of a reference put in its place."""
    return any(readings[k] > limits[k] for k in limits if k in readings)


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 7])
def test_lm_control_fails_and_reference_passes(seed):
    from bench.drivers import lm_train as D
    ctx = tiny_ctx("smollm-360m.s4096", seed)
    got = D.control_readings(ctx)
    limits = ctx.cell["check"]
    assert fails(got["control"], limits), got["control"]
    ref = D.reference_readings(ctx)
    same = D.numbers(ref, ref)
    assert not fails(same, limits)


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 7])
def test_svm_control_reads_above_program(seed, monkeypatch):
    from bench import faults
    from bench.drivers import svm_dms as D
    # one worker here: the exchange fault is test_faults' to catch
    monkeypatch.setattr(faults, "SVM", tuple(
        f for f in faults.SVM if f != "no_exchange"))
    for cell in T.cells_driven_by("svm_dms"):
        ctx = tiny_ctx(cell, seed)
        got = D.control_readings(ctx)
        limits = ctx.cell["check"]
        assert not fails(got["program"], limits), (cell, got["program"])
        assert fails(got["control"], limits), (cell, got["control"])
        assert got["control"]["w_rel_l2"] > 10 * got["program"]["w_rel_l2"]
        for f in faults.SVM:
            assert fails(got[f], limits), (cell, f, got[f])
