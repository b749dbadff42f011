"""The program's tracing names: device scopes in the compiled HLO's
op_names, host spans and counters of the step loop, and the DMS job
accounting the trace reduction divides by."""
import glob
import json
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import run_with_devices
from repro.checkpoint import CheckpointManager
from repro.config import (CheckpointConfig, DataConfig, FaultToleranceConfig,
                          ModelConfig)
from repro.core import svm, telemetry
from repro.data.pipeline import DataPipeline
from repro.runtime import StepRunner

# an instruction line of HLO text: name, op kind, op_name
INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(.*'
                   r'metadata=\{op_name="([^"]*)"')


def instructions(text):
    """(name, kind, op_name) of every instruction that has an op_name."""
    return [m.groups() for m in map(INSTR.match, text.splitlines()) if m]


def test_names_take_the_prefix_and_the_vocabulary():
    assert all(not n.startswith(telemetry.PREFIX) for n in
               telemetry.SCOPES + telemetry.SPANS)
    assert len(set(telemetry.SCOPES)) == len(telemetry.SCOPES)
    with pytest.raises(ValueError):
        telemetry.scope("svm.blocks")
    with pytest.raises(ValueError):
        telemetry.span("dispatched")

    @jax.jit
    def f(x):
        with telemetry.scope("lm.mlp"):
            return jnp.tanh(x) * 2
    text = f.lower(jnp.ones(4)).as_text(debug_info=True)
    assert "repro.lm.mlp" in text


_CACHED = """
import os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
import jax, jax.numpy as jnp
from repro.launch.cache import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

def plain():
    def f(x):
        return jnp.tanh(x) * 2
    return f

def scoped():
    def f(x):
        with jax.named_scope("repro.lm.mlp"):
            return jnp.tanh(x) * 2
    return f

x = jnp.ones(4)
first = jax.jit(plain()).lower(x).compile().as_text()
second = jax.jit(scoped()).lower(x).compile().as_text()
print(len(os.listdir(sys.argv[1])), "repro.lm.mlp" in first,
      "repro.lm.mlp" in second)
"""


def test_compile_cache_keys_programs_by_their_scopes(tmp_path):
    """A program that differs from a cached one only in its scopes gets
    an executable of its own, whose operations carry the scopes."""
    out = run_with_devices(_CACHED.replace("sys.argv[1]",
                                           repr(str(tmp_path))),
                           n_devices=1)
    entries, first, second = out.split()
    assert int(entries) >= 2          # both went to the cache
    assert (first, second) == ("False", "True")


_DMS = """
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import svm
mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
rx = re.compile(r'^\\s*(?:ROOT )?%([\\w.\\-]+) = .*? ([a-z][\\w\\-]*)\\(.*'
                r'metadata=\\{op_name="([^"]*)"')
out = {}
for overlap, topology in [("none", "all"), ("delayed", "all"),
                          ("chunked", "all"), ("none", "ring"),
                          ("none", "pairwise")]:
    fn = svm.dms_shard_map_program(mesh, "data", epochs=2, block_size=8,
                                   overlap=overlap, topology=topology)
    w = jnp.zeros((22,))
    xs, ys = jnp.ones((2, 48, 22)), jnp.ones((2, 48))
    text = fn.lower(w, xs, ys).compile().as_text()
    out[overlap + "/" + topology] = [m.groups() for m in
                                     map(rx.match, text.splitlines()) if m]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dms_instructions():
    out = run_with_devices(_DMS, n_devices=2)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["none/all", "delayed/all", "chunked/all",
                                  "none/ring", "none/pairwise"])
def test_dms_collectives_and_block_carry_their_scopes(dms_instructions,
                                                      mode):
    ins = dms_instructions[mode]
    coll = [(n, k, op) for n, k, op in ins
            if k in ("all-reduce", "collective-permute", "all-reduce-start",
                     "collective-permute-start")]
    # each block's exchange (in the loop) is an all-reduce or ppermutes;
    # the flush after the loop, where there is one, an all-reduce
    want = "all-reduce" if mode.endswith("/all") else "collective-permute"
    in_loop = [k for _, k, op in coll if "while/body" in op]
    assert in_loop and all(k.startswith(want) for k in in_loop), coll
    assert all("repro.svm.sync" in op for _, _, op in coll), coll
    dots = [op for _, k, op in ins if k in ("dot", "fusion")
            and "dot_general" in op]
    assert dots and all("repro.svm.block" in op for op in dots), dots


def _lm_step_text():
    from repro.config import TrainConfig, get_smoke
    from repro.config.cli import apply_overrides
    from repro.launch.mesh import test_mesh_config
    from repro.launch.train import build_trainer
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    cfg = TrainConfig(model=get_smoke("smollm-360m"),
                      mesh=test_mesh_config((1, 1)),
                      data=DataConfig(seq_len=32, global_batch=2, seed=1),
                      steps=1, remat="full", seed=1)
    cfg = apply_overrides(cfg, ["optimizer.name=adamw"])
    step, state, make_pipeline, _, _, _ = build_trainer(cfg, mesh)
    batch = next(make_pipeline(0))
    with jax.set_mesh(mesh):
        return step.lower(state, batch).compile().as_text()


def test_lm_step_scopes_cover_forward_backward_and_update():
    ins = instructions(_lm_step_text())
    attn = [op for _, k, op in ins if "repro.lm.attention" in op
            and "dot_general" in op]
    # forward, remat recompute and backward all keep the scope
    assert any("transpose" not in op and "rematted" not in op
               for op in attn), attn
    assert any("rematted_computation" in op for op in attn), attn
    assert any("transpose" in op for op in attn), attn
    assert any("repro.lm.mlp" in op and "dot_general" in op
               for _, _, op in ins)
    assert any("repro.lm.loss" in op for _, _, op in ins)
    assert any(k == "sqrt" and "repro.lm.optimizer" in op
               for _, k, op in ins)
    assert all("repro.lm.optimizer" in op for _, k, op in ins
               if k == "sqrt")


def _toy_step(state, batch):
    m = jnp.mean(batch["tokens"].astype(jnp.float32))
    return {"w": state["w"] * 0.9 + 0.1 * m}, {"loss": m}


def _runner(tmp_path, fault=None):
    data_cfg = DataConfig(seq_len=8, global_batch=2, seed=3)
    model_cfg = ModelConfig(vocab_size=97)
    ckpt = CheckpointManager(CheckpointConfig(directory=str(tmp_path)))
    return StepRunner(jax.jit(_toy_step), ckpt,
                      fault or FaultToleranceConfig(), ckpt_interval=2,
                      make_pipeline=lambda s: DataPipeline(
                          data_cfg, model_cfg, start_step=s))


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(telemetry.PREFIX):
                    spans.append((e.name[len(telemetry.PREFIX):],
                                  e.start_ns, e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def test_step_loop_spans_in_order(tmp_path):
    runner = _runner(tmp_path / "ckpt")
    jax.profiler.start_trace(str(tmp_path / "trace"))
    _, step = runner.run({"w": jnp.float32(0)}, 0, 3)
    jax.profiler.stop_trace()
    assert step == 3
    spans = _host_spans(tmp_path / "trace")
    steps = [s for s in spans if s[0] == "step"]
    assert [int(s[3]["step_num"]) for s in steps] == [0, 1, 2]
    saves = 0
    for _, lo, hi, _ in steps:
        inner = [s[0] for s in spans if s[0] != "step" and lo <= s[1]
                 and s[2] <= hi]
        assert inner[:3] == ["data", "dispatch", "fetch"], inner
        saves += inner.count("save")
        assert set(inner) <= {"data", "dispatch", "fetch", "save"}
    assert saves == 1                            # after the second step
    assert set(telemetry.SPANS) >= {s[0] for s in spans}


def test_runner_counts_steps_saves_and_restarts(tmp_path):
    runner = _runner(tmp_path / "a")
    runner.run({"w": jnp.float32(0)}, 0, 5)
    assert (runner.steps, runner.saves, runner.restarts) == (5, 2, 0)
    # a fault at step 3 restores the step-2 save and replays step 2
    replay = _runner(tmp_path / "b", FaultToleranceConfig(inject_failure_at=3))
    replay.run({"w": jnp.float32(0)}, 0, 5)
    assert (replay.steps, replay.saves, replay.restarts) == (6, 2, 1)
    assert len(replay.metrics_log) == replay.steps


# hand counts for 2 epochs of 3 blocks of 8 rows, d = 22 (chunked pads it
# to 24 for 4 segments of 6)
@pytest.mark.parametrize("overlap,topology,syncs,values", [
    ("none", "all", 6, 6 * 22),
    ("delayed", "all", 7, 7 * 22),
    ("chunked", "all", 7, 6 * 6 + 24),
    ("none", "ring", 7, 7 * 22),
    ("none", "pairwise", 7, 7 * 22),
    ("delayed", "ring", 7, 7 * 22),
])
def test_dms_job_counts(overlap, topology, syncs, values):
    got = svm.dms_job_counts(24 + 5, 22, 8, 2, overlap=overlap,
                             topology=topology)
    assert got == {"blocks": 6, "syncs": syncs, "sync_bytes": 4 * values,
                   "samples": 48}
