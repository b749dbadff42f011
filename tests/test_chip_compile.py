"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler (libtpu) compiles for a topology that is described
rather than present, so these tests catch what the chip's
compiler would refuse (unaligned tiles, VMEM overuse, unpartitionable
kernels) at no chip time. Nothing runs, so they say nothing about results
or times. The topology is described inside a fixture: only one process at a
time may load the TPU library, and describing it while a module is
imported would make test collection differ between pytest workers.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.kernels

D_PAPER = (22, 254, 2000)          # ijcnn1, webspam, epsilon widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile cannot be read back without a chip: keep
    it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """This process' backend is the CPU, which would pick the Pallas
    interpreter; the described chip compiles the kernel."""
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("d", D_PAPER)
def test_hinge_kernel_compiles(topo, no_compile_cache, compiled_kernels, d):
    from repro.kernels.hinge import ops
    one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    n = 512
    compiled = jax.jit(lambda w, x, y: ops.hinge_block_grad(w, x, y, 1.0)
                       ).lower(_sds((d,), one), _sds((n, d), one),
                               _sds((n,), one)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chips,topology", [(1, "all"), (4, "all"),
                                            (4, "ring")])
def test_dms_shard_map_compiles(topo, no_compile_cache, compiled_kernels,
                                chips, topology):
    from repro.core import svm
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    n_local, d = 4096, 2000
    fn = svm.dms_shard_map_program(mesh, "data", epochs=1, block_size=64,
                                   grad_impl="pallas", topology=topology)
    compiled = fn.lower(
        _sds((d,), NamedSharding(mesh, P())),
        _sds((chips, n_local, d), NamedSharding(mesh, P("data"))),
        _sds((chips, n_local), NamedSharding(mesh, P("data")))).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    if chips > 1:
        want = "all-reduce" if topology == "all" else "collective-permute"
        assert want in hlo
    # the program's scopes survive the chip compiler's passes: the
    # kernel is the block's, every collective the exchange's
    lines = [ln for ln in hlo.splitlines() if " = " in ln]
    assert all("repro.svm.block" in ln for ln in lines
               if "tpu_custom_call" in ln)
    colls = [ln for ln in lines if re.search(
        r"\s(all-reduce|collective-permute)(-start)?\(", ln)]
    assert all("repro.svm.sync" in ln for ln in colls), colls
    assert bool(colls) == (chips > 1)


@pytest.mark.parametrize("b,s,h,kv,hd", [
    (2, 4096, 15, 5, 64),          # smollm-360m's GQA, 1024-blocks
    (1, 4096, 16, 1, 128),         # MQA, a full-width head
    (1, 4096, 8, 8, 256),          # MHA, the widest head taken
    (2, 640, 4, 2, 256),           # 128-blocks
])
def test_flash_kernel_compiles(topo, no_compile_cache, compiled_kernels,
                               b, s, h, kv, hd):
    """The splash kernels, forward and fused backward, fit the chip's
    VMEM at the block sizes ``_splash_plan`` picks."""
    from repro.models import attention as A
    one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((b, s, kv, hd), jnp.bfloat16, sharding=one)
    grads = jax.grad(lambda q, k, v: jnp.sum(
        A._sdpa_flash(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    assert "tpu_custom_call" in jax.jit(grads).lower(
        q, k, k).compile().as_text()


def _lm_step_text(topo, chips: int, batch: int, seq: int = 4096,
                  model_axis: int = 1, arch_cfg=None) -> str:
    """The smollm-360m DDP step (widths as published, 2 of its 32 layers,
    AdamW, remat full), or ``arch_cfg``'s, compiled for ``chips``
    described chips, a data axis of ``chips // model_axis`` holding the
    batch by a model axis."""
    import dataclasses
    import functools
    from repro.config import TrainConfig, get_arch
    from repro.config.base import DataConfig
    from repro.config.cli import apply_overrides
    from repro.core import local_sgd as LS
    from repro.launch.mesh import test_mesh_config
    from repro.models.registry import build_model
    from repro.sharding import rules_for

    shape = (chips // model_axis, model_axis)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(shape),
                ("data", "model"))
    cfg = TrainConfig(
        model=arch_cfg or dataclasses.replace(get_arch("smollm-360m"),
                                              n_layers=2),
        mesh=test_mesh_config(shape),
        data=DataConfig(seq_len=seq, global_batch=batch), remat="full")
    cfg = apply_overrides(cfg, ["optimizer.name=adamw"])
    model = build_model(cfg.model, scan_layers=cfg.scan_layers,
                        remat=cfg.remat)
    rules = rules_for(cfg.mesh, mesh)
    shapes = jax.eval_shape(functools.partial(LS.init_state, model, cfg,
                                              replicas=0),
                            jax.random.key(0))
    shardings = LS.state_shardings(
        LS.build_state_axes(model, cfg, replicated=False), rules,
        jax.tree.map(lambda x: x.shape, shapes))
    state = jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh), shapes, shardings)
    rows = NamedSharding(mesh, P("data"))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows)
    step = LS.make_train_step(model, cfg, mesh, rules)
    with jax.set_mesh(mesh):
        return jax.jit(step).lower(
            state, {"tokens": tokens, "targets": tokens}).compile().as_text()


def _result_shapes(hlo: str, kind: str):
    """The result shapes of every ``kind`` instruction (or its async
    start) in the text."""
    return [ln.split(" = ", 1)[1].split(f" {kind}")[0]
            for ln in hlo.splitlines()
            if re.search(rf"\s{kind}(-start)?\(", ln)]


def test_lm_step_runs_the_flash_kernel(topo, no_compile_cache,
                                       compiled_kernels):
    """One chip, S 4,096: causal self-attention goes through the splash
    kernels, and no (…, 512, 4096) score tensor of the jnp chunked path
    is left in the step."""
    from repro.core.telemetry import ATTENTION
    before = ATTENTION.counts()
    hlo = _lm_step_text(topo, chips=1, batch=2)
    assert ATTENTION.since(before) == {"flash": 2, "chunked": 0,
                                       "full": 0, "pallas": 0}
    kernels = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call",
                         hlo)
    assert kernels and all(k.startswith("splash_mqa_") for k in kernels)
    # the forward (and its remat recompute) and the fused backward
    assert {k.split("_")[2] for k in kernels} == {"fwd", "dkv"}
    assert re.search(r'op_name="[^"]*repro\.lm\.attention/[^"]*splash', hlo)
    assert not re.search(r"f32\[[\d,]*512,4096\]", hlo)


def test_lm_step_flash_kernel_per_shard(topo, no_compile_cache,
                                        compiled_kernels):
    """A 2x2 with the batch over the 4 chips: each chip runs the kernel
    on its own rows, so no activation (a shape holding the sequence
    length) is all-gathered ahead of it."""
    hlo = _lm_step_text(topo, chips=4, batch=8)
    assert "tpu_custom_call" in hlo
    gathered = _result_shapes(hlo, "all-gather")
    assert gathered                   # the weights' FSDP gathers are there
    assert not [s for s in gathered if "4096" in s], gathered


def test_lm_step_keeps_attention_split_over_a_model_axis(
        topo, no_compile_cache, compiled_kernels):
    """A 2x2 of data by model with smollm-360m's 15/5 heads: no head
    split divides the model axis, so the kernel, which the compiler
    cannot partition, would run the same attention on both of its chips.
    The step takes the jnp chunked path instead, whose score rows are
    split over that axis (256 of each 512-row chunk a chip)."""
    from repro.core.telemetry import ATTENTION
    before = ATTENTION.counts()
    hlo = _lm_step_text(topo, chips=4, batch=8, model_axis=2)
    assert ATTENTION.since(before) == {"flash": 0, "chunked": 2,
                                       "full": 0, "pallas": 0}
    assert "tpu_custom_call" not in hlo
    assert re.search(r"f32\[4,5,3,256,4096\]", hlo)
    assert not re.search(r"f32\[[\d,]*512,4096\]", hlo)


def test_hybrid_step_runs_the_flash_kernel_and_the_ssd_scan(
        topo, no_compile_cache, compiled_kernels):
    """granite-4.0-h-micro's widths (one Mamba-2 and one NoPE attention
    layer of its published ones, the vocabulary's eighth) at S 8,192 on
    one chip: the attention layer, scaled by 1/64, takes the splash
    kernels, the Mamba-2 layer's SSD compiles as the chunked scan, and
    the mixer's forward, remat and backward ops carry its scopes."""
    import dataclasses
    from repro.config import get_arch
    from repro.core.telemetry import ATTENTION, SSD
    cfg = dataclasses.replace(get_arch("granite-4.0-h-micro"), n_layers=2,
                              layer_types=("mamba", "attention"),
                              vocab_size=12_544)
    attn0, ssd0 = ATTENTION.counts(), SSD.counts()
    hlo = _lm_step_text(topo, chips=1, batch=1, seq=8192, arch_cfg=cfg)
    assert ATTENTION.since(attn0) == {"flash": 1, "chunked": 0, "full": 0,
                                      "pallas": 0}
    assert SSD.since(ssd0) == {"chunked": 1}
    kernels = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call",
                         hlo)
    assert kernels and all(k.startswith("splash_mqa_") for k in kernels)
    names = re.findall(r'op_name="([^"]*repro\.lm\.ss[md][^"]*)"', hlo)
    assert any("/repro.lm.ssm/repro.lm.ssd/" in n for n in names)
    assert any("transpose(" in n and "repro.lm.ssd" in n for n in names)
    assert any("rematted_computation" in n and "repro.lm.ssm" in n
               for n in names)
