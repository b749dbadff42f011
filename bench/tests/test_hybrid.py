"""The interleaved hybrid's cell (``granite-4.0-h-micro.s8192``), reached by
name through the harness at its tiny size: its control and the faults
planted in its reference read above the cell's limits, while the
reference read against itself passes; every fault planted under the
timed path, the SSD state left uncarried across chunks among them, makes
a run not correct; and the counts behind ``mfu.hybrid`` and
``ssd_roofline.lm`` hold the program's own shapes."""
import json
import os

import pytest

from bench import counts_hybrid, faults, hybrid_faults
from bench.drivers import hybrid_train as D
from bench.tests.conftest import cells_driven_by
from bench.tests.test_controls import fails, tiny_ctx

CELLS = cells_driven_by("hybrid_train")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def published() -> dict:
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_the_cell_is_driven_here():
    assert CELLS == ["granite-4.0-h-micro.s8192"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_reference_faults_fail_and_reference_passes(cell):
    ctx = tiny_ctx(cell, 2 ** 31 + 7)
    got = D.control_readings(ctx)
    limits = ctx.cell["check"]
    assert set(got) == {"control", *D.REFERENCE_FAULTS}
    for name, readings in got.items():
        assert fails(readings, limits), (name, readings)
    ref = D.reference_readings(ctx)
    assert not fails(D.LT.numbers(ref, ref), limits)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", faults.LM + hybrid_faults.HYBRID)
def test_fault_is_not_correct(harness, cell, fault):
    planted = (hybrid_faults.hybrid if fault in hybrid_faults.HYBRID
               else faults.lm)
    with planted(fault):
        out = harness.run_cell(cell, 2 ** 31 + 3, 0.5, False)
    assert out["correct"] is False, (fault, out["check"])


def test_planted_ssd_fault_is_undone():
    from repro.models import ssm
    real = ssm.ssd_chunked
    with hybrid_faults.hybrid("ssd_state_not_carried"):
        assert ssm.ssd_chunked is not real
    assert ssm.ssd_chunked is real
    with pytest.raises(ValueError):
        with hybrid_faults.hybrid("no_such_fault"):
            pass


def test_counts_match_the_program():
    """The parameters counted from the published keys are the program's
    own (``analytic_param_count`` of the configuration the driver
    builds): 9 Mamba-2 layers, one attention layer, the vocabulary's
    eighth."""
    from repro.models.registry import analytic_param_count
    c = published()
    cfg = D._model_config(c)
    assert counts_hybrid.hybrid_param_count(c) == 772_160_448
    assert analytic_param_count(cfg) == 772_160_448
    norms = 2 * 10 * 2048 + 2048 + 9 * 4096          # RMSNorm scales
    conv = 9 * 5 * (4096 + 2 * 128)                  # taps and biases
    per_head = 9 * 3 * 64                            # dt_bias, A_log, D
    assert counts_hybrid.hybrid_matmul_params(c) \
        == 772_160_448 - norms - conv - per_head


def test_flops_and_ssd_work_per_token():
    c = published()
    ssd = 2 * 256 * 128 + 256 * 64 * 64 + 4 * 64 * 128 * 64
    assert counts_hybrid.ssd_forward_flops_per_token(c) == ssd
    f = counts_hybrid.hybrid_train_flops_per_token(c, 8192)
    attn = 6 * 1 * 8192 * 32 * 64
    assert f == 6 * counts_hybrid.hybrid_matmul_params(c) + attn \
        + 3 * 9 * ssd
    assert f == pytest.approx(4.82e9, rel=1e-3)
    flops, nbytes = counts_hybrid.ssd_train_work(c, 8192)
    assert flops == 9 * 8192 * 3 * ssd
    state = 4 * 64 * 128 * 64 / 256
    io = 2 * (2 * 64 * 64 + 64 + 2 * 128)
    assert nbytes == pytest.approx(9 * 8192 * (2 * io + state))
