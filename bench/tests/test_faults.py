"""A run with the timed path broken underneath comes out not correct:
once for each fault a cell can have (``bench.faults``). The harness's
look for a chip is steered here; the rest of the run is the harness's.
The four-worker SVM cell runs on four virtual CPU devices in a child
process, so that the exchange between workers exists; a one-worker cell
has no exchange to leave out."""
import json
import os
import subprocess
import sys

import pytest

from bench import faults
from bench.tests.conftest import cells_driven_by

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("fault", faults.LM)
def test_lm_fault_is_not_correct(harness, fault):
    with faults.lm(fault):
        out = harness.run_cell("smollm-360m.s4096", 2 ** 31 + 3, 0.5,
                               False)
    assert out["correct"] is False, (fault, out["check"])


@pytest.mark.parametrize("cell", cells_driven_by("svm_dms", chips=(1,)))
@pytest.mark.parametrize("fault", [f for f in faults.SVM
                                   if f != "no_exchange"])
def test_svm_one_chip_fault_is_not_correct(harness, cell, fault):
    """A one-worker cell exchanges nothing: every other fault."""
    with faults.svm(fault):
        out = harness.run_cell(cell, 2 ** 31 + 9, 0.5, False)
    assert out["correct"] is False, (fault, out["check"])


SVM_CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import faults, run as R
from bench.tests import conftest as T
real = R.load_json
def load(*p):
    d = real(*p)
    if p[-2] in ("configs", "workloads"):
        d = T.shrink(p, d)
    if p[-2] == "workloads":
        d["chips"] = 4
    return d
R.load_json = load
R.require_devices = lambda chips: jax.devices()[:chips]
import repro.launch.cache
repro.launch.cache.use_compile_cache = lambda: "off"
out = {{"sound": R.run_cell("svm-epsilon.k4.b64", 2**31 + 9, 0.5,
                            False)}}
for f in faults.SVM:
    with faults.svm(f):
        out[f] = R.run_cell("svm-epsilon.k4.b64", 2**31 + 9, 0.5, False)
print(json.dumps({{k: [v["correct"], v["check"]] for k, v in out.items()}}))
"""


def test_svm_faults_are_not_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = SVM_CHILD.format(root=ROOT, src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["sound"][0] is True, got["sound"]
    for f in faults.SVM:
        assert got[f][0] is False, (f, got[f])
