"""granite-4.0-h-micro: the interleaved Mamba-2/attention hybrid through
``build_model`` against the plain float32 reference of the benchmark
(``bench/references/granite_ref.py``, which runs the Mamba-2 recurrence
step by step, not the chunked algorithm), and the counts of its SSD and
attention calls."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import granite_ref
from repro.config import get_arch, get_smoke
from repro.core.telemetry import ATTENTION, SSD
from repro.models import hybrid as Hy
from repro.models.registry import analytic_param_count, build_model

ARCH = "granite-4.0-h-micro"


def published(cfg) -> dict:
    """The config.json keys of a granite ModelConfig, as the reference
    reads them."""
    s = cfg.ssm
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "shared_intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "layer_types": list(cfg.layer_types),
        "mamba_n_heads": s.expand * cfg.d_model // s.head_dim,
        "mamba_d_head": s.head_dim, "mamba_d_state": s.state_dim,
        "mamba_d_conv": s.conv_width, "mamba_chunk_size": s.chunk_size,
        "attention_multiplier": cfg.attention_multiplier,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling}


def mamba2_weights(model, key):
    """Seeded weights with Mamba-2's own A and Δ initialisation: A_log =
    log U[1, 16], dt_bias = softplus⁻¹(Δ), Δ log-uniform in [1e-3, 0.1],
    so that decays are realistic and state crosses chunks; conv biases
    and the other projections random, so that every path carries
    gradient."""
    params = model.init(key)
    m = params["layers"]["mamba"]["mixer"]
    ks = jax.random.split(jax.random.fold_in(key, 1), 4)
    dt = jnp.exp(jax.random.uniform(ks[0], m["dt_bias"].shape, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    m["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    m["a_log"] = jnp.log(jax.random.uniform(ks[1], m["a_log"].shape,
                                            jnp.float32, 1.0, 16.0))
    for name, k in (("conv_x_b", ks[2]), ("conv_b_b", ks[3])):
        m[name] = 0.1 * jax.random.normal(k, m[name].shape)
    return params


def batch(cfg, s, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (2, s + 1))
    return {"tokens": jnp.asarray(ids[:, :-1], jnp.int32),
            "targets": jnp.asarray(ids[:, 1:], jnp.int32)}


@pytest.mark.parametrize("remat", ["none", "full"])
def test_model_matches_the_reference(remat):
    """Loss and the gradient of every leaf at the smoke size in float32,
    S 64 = 8 SSD chunks of 8, so that the state crosses 7 chunk
    boundaries. The program's chunked scan and the reference's step-by-
    step recurrence are the same sums in other orders: the loss agrees to
    float32 rounding over a few hundred terms (rtol 1e-5), each gradient
    leaf to 1e-4 of its largest entry (sums over 64 steps and two rows,
    then a backward through 3 layers)."""
    cfg = dataclasses.replace(get_smoke(ARCH), dtype="float32")
    model = build_model(cfg, remat=remat)
    params = mamba2_weights(model, jax.random.key(3))
    b = batch(cfg, 64)
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, b)
    want, want_g = granite_ref.loss_and_grad(
        published(cfg), "highest", True,
        granite_ref.unstack(params, cfg.layer_types), b["tokens"],
        b["targets"])
    want_g = granite_ref.stack(want_g, cfg.layer_types)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    flat = jax.tree.flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(want_g)) == 32
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        w = np.asarray(w)
        assert np.max(np.abs(w)) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), w, rtol=0,
                                   atol=1e-4 * np.max(np.abs(w)),
                                   err_msg=jax.tree_util.keystr(path))


def test_state_carried_across_chunks_matters():
    """The reference with the SSD state reset at every chunk (the planted
    fault) moves the gradients of what shapes the state, B, C, Δ and A,
    by far more than the tolerance above: the comparison would see a
    program that dropped the carry."""
    cfg = dataclasses.replace(get_smoke(ARCH), dtype="float32")
    params = mamba2_weights(build_model(cfg), jax.random.key(3))
    b = batch(cfg, 64)
    c = published(cfg)
    params = granite_ref.unstack(params, cfg.layer_types)
    _, carried = granite_ref.loss_and_grad(c, "highest", True, params,
                                           b["tokens"], b["targets"])
    _, alone = granite_ref.loss_and_grad(c, "highest", False, params,
                                         b["tokens"], b["targets"])
    for name in ("in_b", "in_c", "in_dt", "a_log"):
        g = np.asarray(carried["layers"][0]["mixer"][name])
        f = np.asarray(alone["layers"][0]["mixer"][name])
        assert np.linalg.norm(g - f) > 0.1 * np.linalg.norm(g), name


def test_multipliers_are_applied():
    """Each µP multiplier changes the loss, and at 1 is left out."""
    cfg = dataclasses.replace(get_smoke(ARCH), dtype="float32")
    params = build_model(cfg).init(jax.random.key(0))
    b = batch(cfg, 16)
    base = float(build_model(cfg).loss(params, b)[0])
    for field in ("embedding_multiplier", "residual_multiplier",
                  "logits_scaling"):
        other = dataclasses.replace(cfg, **{field: 1.0})
        assert abs(float(build_model(other).loss(params, b)[0]) - base) \
            > 1e-4, field


def test_full_config_is_the_published_model():
    cfg = get_arch(ARCH)
    assert cfg.layer_types.count("attention") == 4
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert Hy.layer_period(cfg.layer_types) == 10
    assert analytic_param_count(cfg) == 3_191_396_096
    one_period = dataclasses.replace(cfg, n_layers=10,
                                     layer_types=cfg.layer_types[:10],
                                     vocab_size=12_544)
    assert analytic_param_count(one_period) == 772_160_448


@pytest.mark.parametrize("kinds,runs", [
    (("mamba", "attention", "mamba"),
     [("mamba", 0, 1), ("attention", 0, 1), ("mamba", 1, 1)]),
    (("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
     [("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4)]),
])
def test_period_runs(kinds, runs):
    assert Hy._runs(kinds) == runs
    assert Hy.layer_period(kinds * 3) == len(kinds)


@pytest.mark.parametrize("periods", [1, 2])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_counts_one_ssd_and_attention_call_a_layer(periods, scan_layers):
    """The trace-time counters count each layer once, across the scans
    over periods and over runs inside one."""
    smoke = get_smoke(ARCH)
    kinds = smoke.layer_types * periods
    cfg = dataclasses.replace(smoke, n_layers=len(kinds), layer_types=kinds)
    model = build_model(cfg, scan_layers=scan_layers, remat="full")
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    attn0, ssd0 = ATTENTION.counts(), SSD.counts()
    jax.eval_shape(jax.grad(lambda p, b: model.loss(p, b)[0]),
                   jax.eval_shape(model.init, jax.random.key(0)),
                   {"tokens": tokens, "targets": tokens})
    assert SSD.since(ssd0) == {"chunked": 2 * periods}
    assert ATTENTION.since(attn0) == {"flash": 0, "chunked": 0,
                                      "full": periods, "pallas": 0}


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-1.2b"])
def test_ssd_counter_in_the_other_ssm_models(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    before = SSD.counts()
    jax.eval_shape(lambda p, b: model.loss(p, b)[0],
                   jax.eval_shape(model.init, jax.random.key(0)),
                   {"tokens": tokens, "targets": tokens})
    assert SSD.since(before) == {"chunked": cfg.n_layers}
