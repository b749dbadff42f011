"""The yardstick's counts and peak table."""
import json
import os

import pytest

from bench import counts, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def smollm():
    with open(os.path.join(BENCH, "configs", "smollm-360m.json")) as f:
        return json.load(f)["config"]


def test_smollm_parameter_count():
    assert counts.lm_param_count(smollm()) == 361_821_120


def test_smollm_flops_per_token():
    c = smollm()
    matmul = counts.lm_matmul_params(c)
    # every parameter but the 65 RMSNorm scales multiplies a token once
    assert matmul == 361_821_120 - 65 * 960
    attn = 6 * 32 * 4096 * 15 * 64            # causal: half the square
    f = counts.lm_train_flops_per_token(c, 4096)
    assert f == 6 * matmul + attn
    assert f == pytest.approx(2.93e9, rel=2e-3)


def test_svm_work_per_sample():
    assert counts.svm_bytes_per_sample(2000) == 8004
    assert counts.svm_flops_per_sample(2000) == 8000


def test_peaks_known_kind():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["source"]


def test_peaks_unknown_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")


@pytest.mark.parametrize("n_local,block,epochs,overlap,topology", [
    (80_000, 64, 12, "none", "all"), (320_000, 64, 12, "none", "all"),
    (1000, 7, 3, "none", "ring"), (1000, 8, 2, "delayed", "all")])
def test_svm_job_counts_match_the_programs(n_local, block, epochs, overlap,
                                           topology):
    from repro.core import svm
    got = counts.svm_job_counts(n_local, block, epochs, overlap, topology)
    want = svm.dms_job_counts(n_local, 2000, block, epochs, overlap=overlap,
                              topology=topology)
    assert got == {k: want[k] for k in ("blocks", "syncs")}


def test_svm_job_counts_of_the_cells():
    assert counts.svm_job_counts(80_000, 64, 12)["syncs"] == 15_000
    assert counts.svm_job_counts(320_000, 64, 12)["blocks"] == 60_000
