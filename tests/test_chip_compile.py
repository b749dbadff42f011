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
