"""The LM attention's two kinds of path and the rule that picks one.

Causal self-attention at a lane-aligned length on TPU devices runs the
fused splash kernel (``_sdpa_flash``); everything else runs the jnp paths.
Here the kernel runs in Pallas interpret mode on the CPU; the placement
check (``_on_tpu``) is steered in the tests, never by an option of the
program. The compiled kernel on a described chip is in
``tests/test_chip_compile.py``.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.config import get_smoke
from repro.core.telemetry import ATTENTION, PathCounts
from repro.models import attention as A
from repro.models import layers as L
from repro.models.registry import build_model
from repro.sharding import ShardingRules, use_rules

NONE = {"flash": 0, "chunked": 0, "full": 0, "pallas": 0}


def _qkv(b, s, h, kv, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    ct = jax.random.normal(ks[3], (b, s, h, hd), jnp.float32)
    return [x.astype(dtype) for x in (q, k, v, ct)]


def _out_and_grads(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *vjp(ct))]


def _flash(q, k, v, scale=None):
    return A._sdpa_flash(q, k, v, interpret=True, scale=scale)


def _jnp(q, k, v, scale=None):
    return A._sdpa_jnp(q, k, v, A.make_mask(q.shape[1], k.shape[1],
                                            "causal"), scale)


def _max_err(got, want):
    return [float(np.max(np.abs(g - w))) for g, w in zip(got, want)]


@pytest.mark.parametrize("heads,kv_heads,scale",
                         [(15, 5, None), (4, 4, None), (8, 2, 1 / 64)],
                         ids=["smollm-gqa", "mha", "granite-scale"])
@pytest.mark.parametrize("seq", [256, 512])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_matches_jnp(heads, kv_heads, scale, seq, dtype):
    """Output and the gradients of q, k and v (``jax.vjp``) against the
    jnp path with ``make_mask``'s causal mask, at the default softmax
    scale 1/sqrt(hd) and at granite's 1/64. float32: equal within a
    tolerance fixed beforehand from float32 rounding over a 512-long
    row. bfloat16: each error against the float32 answer at most twice
    the jnp path's own bfloat16 error."""
    q, k, v, ct = _qkv(2, seq, heads, kv_heads, 64, jnp.dtype(dtype))
    flash = _out_and_grads(lambda *a: _flash(*a, scale=scale), q, k, v, ct)
    ref = _out_and_grads(lambda *a: _jnp(*a, scale=scale), q, k, v, ct)
    if dtype == "float32":
        for got, want in zip(flash, ref):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        return
    exact = _out_and_grads(lambda *a: _jnp(*a, scale=scale),
                           *[x.astype(jnp.float32) for x in (q, k, v, ct)])
    own = _max_err(ref, exact)
    err = _max_err(flash, exact)
    assert all(e <= 2 * o for e, o in zip(err, own)), (err, own)


@pytest.mark.parametrize("tpu,mask,cross,seq,hd,want", [
    (True, "causal", False, 512, 64, "flash"),
    (True, "causal", False, 4096, 64, "flash"),
    (True, "causal", False, 640, 64, "flash"),       # 128-blocks
    (True, "causal", False, 4096, 256, "flash"),
    (True, "causal", False, 4096, 320, "chunked"),   # head too wide
    (False, "causal", False, 4096, 64, "chunked"),   # CPU
    (False, "causal", False, 512, 64, "full"),
    (True, "prefix", False, 4096, 64, "chunked"),
    (True, "prefix", False, 512, 64, "full"),
    (True, "full", False, 512, 64, "full"),
    (True, "causal", True, 512, 64, "full"),         # cross-attention
    (True, "causal", False, 200, 64, "full"),        # not lane-aligned
    (True, "causal", False, 256, 64, "full"),        # under 512
])
def test_path_choice(monkeypatch, tpu, mask, cross, seq, hd, want):
    monkeypatch.setattr(A, "_on_tpu", lambda: tpu)
    assert A._attention_path((2, seq, 15, hd), 5, mask, cross) == want


def test_blocks_divide_the_sequence():
    def blocks(seq):
        sizes = A._splash_plan(seq, 3)[1]
        return (sizes.block_q, sizes.block_kv_compute, sizes.block_kv_dkv,
                sizes.use_fused_bwd_kernel)
    assert blocks(4096) == (1024, 512, 1024, True)
    assert blocks(512) == (512, 512, 512, True)
    assert blocks(768) == (256, 256, 256, True)
    assert blocks(640) == (128, 128, 128, True)
    assert A._splash_plan(4096, 3) is A._splash_plan(4096, 3)


def _rules_over(platform):
    devices = np.array([types.SimpleNamespace(platform=platform)] * 2)
    mesh = types.SimpleNamespace(devices=devices.reshape(2, 1),
                                 axis_names=("data", "model"))
    return ShardingRules({"batch": ("data",)}, mesh)


def _rules_on(shape, platform="tpu"):
    """Default rules over a described data x model mesh of ``shape``."""
    from repro.config import MeshConfig
    from repro.sharding import rules_for
    axes = ("data", "model")
    devices = np.array([types.SimpleNamespace(platform=platform)]
                       * int(np.prod(shape))).reshape(shape)
    mesh = types.SimpleNamespace(devices=devices, axis_names=axes,
                                 shape=dict(zip(axes, shape)))
    return rules_for(MeshConfig(shape=shape, axis_names=axes), mesh)


@pytest.mark.parametrize("shape,b,h,kv,want", [
    ((1, 1), 2, 15, 5, ("data",)),                 # one chip
    ((4, 1), 8, 15, 5, ("data",)),                 # batch over 4
    ((2, 2), 8, 4, 2, ("data", None, "model")),    # whole KV groups
    ((16, 16), 32, 64, 16, ("data", None, "model")),
    ((4, 1), 2, 15, 5, None),        # batch does not divide: replicated
    ((2, 2), 8, 15, 5, None),        # smollm: no head split divides
    ((16, 16), 32, 32, 8, None),     # phi3.5: heads divide, KV do not
    ((16, 16), 32, 16, 2, None),     # qwen2.5
    ((16, 16), 32, 24, 8, None),     # llama
])
def test_flash_takes_only_layouts_that_split_every_axis(shape, b, h, kv,
                                                         want):
    """The kernel's call is manual over the mesh: every axis of more
    than one device has to split batch or whole KV groups, or each chip
    on it would run the same attention; there the jnp paths take it."""
    with use_rules(_rules_on(shape)):
        spec = A._flash_spec(b, h, kv)
        path = A._attention_path((b, 4096, h, 64), kv, "causal", False)
    assert (None if spec is None else tuple(spec)) == want
    assert path == ("chunked" if want is None else "flash")


def test_placement_is_read_from_the_rules_mesh():
    assert not A._on_tpu()                       # this process' CPU
    with use_rules(_rules_over("tpu")):
        assert A._on_tpu()
    with use_rules(_rules_over("cpu")):
        assert not A._on_tpu()


def test_path_counts_repeat_and_reject_unknown_paths():
    c = PathCounts(("a", "b"))
    before = c.counts()
    c.add("a")
    with c.repeated(4):
        c.add("b")
        with c.repeated(2):
            c.add("a")
    c.add("b")
    assert c.since(before) == {"a": 9, "b": 5}
    with pytest.raises(ValueError):
        c.add("c")


def _traced_calls(model, batch, params=None):
    before = ATTENTION.counts()
    params = params or jax.eval_shape(model.init, jax.random.key(0))
    jax.eval_shape(jax.grad(lambda p, b: model.loss(p, b)[0]), params,
                   batch)
    return ATTENTION.since(before)


def _lm_batch(b, s):
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return {"tokens": tokens, "targets": tokens}


@pytest.mark.parametrize("scan_layers", [True, False])
@pytest.mark.parametrize("tpu,seq,path", [(True, 512, "flash"),
                                          (False, 512, "full"),
                                          (False, 2048, "chunked"),
                                          (True, 200, "full")])
def test_decoder_counts_one_call_a_layer(monkeypatch, scan_layers, tpu,
                                         seq, path):
    monkeypatch.setattr(A, "_on_tpu", lambda: tpu)
    cfg = get_smoke("smollm-360m")
    model = build_model(cfg, scan_layers=scan_layers, remat="full")
    assert _traced_calls(model, _lm_batch(2, seq)) == {
        **NONE, path: cfg.n_layers}


def test_encoder_decoder_keeps_its_jnp_paths(monkeypatch):
    """whisper: the encoder (full mask) and the cross-attention stay on
    the jnp path on TPUs; the decoder's causal self-attention at an
    aligned length takes the kernel."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    cfg = get_smoke("whisper-base")
    model = build_model(cfg)
    batch = {**_lm_batch(2, 512), "frames": jax.ShapeDtypeStruct(
        (2, cfg.n_audio_frames, cfg.d_model), jnp.float32)}
    assert _traced_calls(model, batch) == {
        **NONE, "flash": cfg.n_layers,
        "full": cfg.n_encoder_layers + cfg.n_layers}


def test_full_attention_on_the_flash_path_matches_jnp(monkeypatch):
    """One smollm-shaped attention layer (projections, RoPE, ``wo``) at
    S 512 in float32: the kernel's path against the jnp path."""
    cfg = get_smoke("smollm-360m")
    params = L.init_params(A.attn_defs(cfg), jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, 512, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(512)[None], (2, 512))
    want = A.full_attention(params, x, pos, cfg)
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    before = ATTENTION.counts()
    got = A.full_attention(params, x, pos, cfg)
    assert ATTENTION.since(before) == {**NONE, "flash": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


_SHARDED = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.config import MeshConfig, get_smoke
from repro.launch.mesh import make_test_mesh
from repro.models import attention as A
from repro.models.registry import build_model
from repro.sharding import rules_for, use_rules
import repro.kernels

shape, axes = {shape}, {axes}
# 2 KV heads, so that a 2-wide model axis splits whole groups
cfg = dataclasses.replace(get_smoke("smollm-360m"), n_heads=4, n_kv_heads=2,
                          head_dim=32, dtype="float32")
model = build_model(cfg, remat="full")
params = model.init(jax.random.key(0))
tokens = jax.random.randint(jax.random.key(1), (8, 512), 0, cfg.vocab_size)
batch = {{"tokens": tokens, "targets": tokens}}
mesh = make_test_mesh(shape, axes)
rules = rules_for(MeshConfig(shape=shape, axis_names=axes), mesh)

def loss_grad(p, b):
    with use_rules(rules):
        return jax.value_and_grad(lambda p: model.loss(p, b)[0])(p)

def run():
    with jax.set_mesh(mesh):
        return jax.jit(loss_grad)(params, batch)

want = run()
A._on_tpu = lambda: True
repro.kernels.default_interpret = lambda: True
jax.clear_caches()                  # trace loss_grad anew on the kernel
with jax.set_mesh(mesh):
    hlo = jax.jit(loss_grad).lower(params, batch).as_text()
got = run()
assert "sdy.manual_computation" in hlo
np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-6)
print("OK")
"""


@pytest.mark.parametrize("shape,axes", [((4, 1), ("data", "model")),
                                        ((2, 2), ("data", "model"))],
                         ids=["batch-4", "batch-2-heads-2"])
def test_flash_per_shard_matches_jnp(shape, axes):
    """On 4 virtual devices, the loss and gradients of a model whose
    attention runs the kernel per shard (``shard_map`` over batch, and
    over heads where they divide) match the jnp path's."""
    code = _SHARDED.format(shape=shape, axes=axes)
    assert "OK" in run_with_devices(code, n_devices=4)


_LOCAL_SGD = """
import dataclasses
import jax, numpy as np
from repro.config import TrainConfig, get_smoke
from repro.config.base import DataConfig
from repro.config.cli import apply_overrides
from repro.core.telemetry import ATTENTION
from repro.launch.mesh import make_test_mesh, test_mesh_config
from repro.launch.train import build_trainer
from repro.models import attention as A
import repro.kernels

axes = ("pod", "data", "model")
mesh = make_test_mesh((2, 2, 1), axes)
cfg = TrainConfig(
    model=dataclasses.replace(get_smoke("smollm-360m"), dtype="float32"),
    mesh=test_mesh_config((2, 2, 1), axes),
    data=DataConfig(seq_len=512, global_batch=8, seed=1), steps=1,
    remat="full", seed=1)
cfg = apply_overrides(cfg, ["sync.strategy=periodic", "sync.period=2"])

def block():
    step, state, make_pipeline, *_ = build_trainer(cfg, mesh)
    with jax.set_mesh(mesh):
        state, metrics = step(state, next(make_pipeline(0)))
    return float(metrics["loss"]), jax.tree.leaves(state["params"])

want = block()
A._on_tpu = lambda: True
repro.kernels.default_interpret = lambda: True
jax.clear_caches()
before = ATTENTION.counts()
got = block()
assert ATTENTION.since(before)["flash"] == cfg.model.n_layers
np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
for a, b in zip(got[1], want[1]):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-6)
print("OK")
"""


def test_flash_nests_under_the_local_sgd_replica_axis():
    """Local SGD over 2 replicas of 2 chips: inside the block the replica
    axis is manual already, and the kernel's call is made manual over
    the rest; one sync block matches the jnp path's."""
    assert "OK" in run_with_devices(_LOCAL_SGD, n_devices=4)


def _sdpa_before(q, k, v, mask):
    """The jnp core as it read before the softmax scale became a setting:
    scores divided by sqrt(hd)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = scores / (hd ** 0.5)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(b, s, h, hd)


@pytest.mark.parametrize("path", ["full", "flash"])
def test_smollm_attention_unchanged_under_the_defaults(monkeypatch, path):
    """smollm's config leaves the scale and RoPE at their defaults, and its
    attention is bit for bit what it was: the jnp core divides the
    scores by sqrt(hd) as before; the kernel's q is multiplied by
    hd**-0.5 in q's dtype, as before."""
    cfg = get_smoke("smollm-360m")
    assert cfg.attention_multiplier == 0.0
    assert cfg.position_embedding == "rope"
    q, k, v, _ = _qkv(2, 512, 15, 5, 64, jnp.bfloat16)
    if path == "full":
        mask = A.make_mask(512, 512, "causal")
        got = A._sdpa_jnp(q, k, v, mask)
        want = _sdpa_before(q, k, v, mask)
    else:
        got = _flash(q, k, v)
        want = _flash(q * jnp.asarray(64 ** -0.5, q.dtype), k, v, scale=1.0)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _nope_reference(params, x, scale):
    """Causal GQA attention with no positional embedding and the scores
    times ``scale``, in float32 at full precision."""
    hi = jax.lax.Precision.HIGHEST
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"], precision=hi)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"], precision=hi)
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"], precision=hi)
    b, s, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k, precision=hi) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=hi)
    return jnp.einsum("bshd,hdm->bsm", o.reshape(b, s, h, hd), params["wo"],
                      precision=hi)


@pytest.mark.parametrize("path,seq,tpu", [("full", 64, False),
                                          ("chunked", 2048, False),
                                          ("flash", 512, True)])
def test_nope_and_scale_on_every_path(monkeypatch, path, seq, tpu):
    """granite's attention (no RoPE, scores times 1/64 in place of
    1/sqrt(hd)) through ``full_attention`` on each of the three cores:
    equal to a plain float32 NoPE attention within float32 rounding over
    a row of ``seq`` (the kernel in interpret mode within 1e-4), and the
    same whatever positions it is given."""
    monkeypatch.setattr(A, "_on_tpu", lambda: tpu)
    cfg = dataclasses.replace(get_smoke("granite-4.0-h-micro"), head_dim=64,
                              attention_multiplier=1 / 64, dtype="float32")
    assert cfg.position_embedding == "nope"
    params = L.init_params(A.attn_defs(cfg), jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (1, seq, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(seq)[None], (1, seq))
    before = ATTENTION.counts()
    got = A.full_attention(params, x, pos, cfg)
    moved = A.full_attention(params, x, pos + 1000, cfg)
    assert ATTENTION.since(before) == {**NONE, path: 2}
    want = _nope_reference(params, x, 1 / 64)
    tol = 1e-4 if path == "flash" else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(got))
