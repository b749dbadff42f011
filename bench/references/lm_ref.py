"""A llama-style decoder trained with AdamW, written plainly in float32.

Follows the published architecture of the configurations it runs
(SmolLM2: LlamaForCausalLM): token embedding; per layer RMSNorm, rotary
embedding over the two halves of each head (inverse frequencies
θ^(−i/(hd/2))), grouped-query causal attention (query head h reads
key/value head h // (H/KV)), softmax in float32, output projection,
residual; RMSNorm, SwiGLU MLP (silu(x·W_gate) ⊙ x·W_up)·W_down, residual;
a final RMSNorm and the tied output head; mean next-token cross-entropy.

Departures, for memory only: the batch's rows are taken one at a time and
their gradients averaged (equal token counts, so this is the batch mean),
and each layer is recomputed in the backward pass.

``precision="highest"`` is the reference: float32 matrix products at full
precision. ``"fp8"`` is the control: every product's operands rounded to
float8 e4m3 with one scale per tensor, the step below the bfloat16 the
configuration computes in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x):
    """x rounded to float8 e4m3 under one scale for the tensor. The
    rounding is the forward pass's alone: gradients pass it unchanged
    (left to the cast, they would be rounded to float8 unscaled and
    vanish)."""
    s = jnp.max(jnp.abs(x)) / _E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(precision: str):
    if precision == "highest":
        return functools.partial(jnp.einsum, precision=_HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _fp8(a), _fp8(b),
                                             precision=_HIGHEST)
    raise ValueError(precision)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c: dict, mm, x, lp, cos, sin):
    """One decoder block on one row: x (S, D)."""
    s = x.shape[0]
    h_, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = c["rms_norm_eps"]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _rope(mm("sd,dhk->shk", h, a["wq"]), cos, sin)
    k = _rope(mm("sd,dhk->shk", h, a["wk"]), cos, sin)
    v = mm("sd,dhk->shk", h, a["wv"])
    qg = q.reshape(s, kv, h_ // kv, hd)
    scores = mm("skgd,tkd->kgst", qg, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm("kgst,tkd->skgd", p, v).reshape(s, h_, hd)
    x = x + mm("shk,hkd->sd", o, a["wo"])
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = mm("sd,df->sf", h, m["w_gate"])
    u = mm("sd,df->sf", h, m["w_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"])


def row_loss(c: dict, precision: str, params, tokens, targets):
    """Mean next-token NLL of one row (S,)."""
    mm = _mm(precision)
    s = tokens.shape[0]
    half = c["head_dim"] // 2
    inv = 1.0 / (c["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                     / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    body = jax.checkpoint(lambda x, lp: (_layer(c, mm, x, lp, cos, sin),
                                         None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    logits = mm("sd,vd->sv", x, emb)
    nll = jax.nn.logsumexp(logits, -1) \
        - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.mean(nll)


def loss_and_grad(c: dict, precision: str, params, tokens, targets):
    """Batch loss and gradient: rows one at a time, averaged."""
    vg = jax.value_and_grad(functools.partial(row_loss, c, precision))

    def body(acc, row):
        loss, grad = vg(params, *row)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grad)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (loss, grad), _ = jax.lax.scan(body, zero, (tokens, targets))
    b = tokens.shape[0]
    return loss / b, jax.tree.map(lambda g: g / b, grad)


def adamw(opt: dict, t: int, p, g, m, v):
    """One AdamW update (bias-corrected moments, decoupled decay)."""
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    p = p - opt["learning_rate"] * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                                    + opt["weight_decay"] * p)
    return p, m, v


def make_step(c: dict, opt: dict, precision: str):
    """``step(params, m, v, t, tokens, targets) → (params, m, v, loss,
    gradient norms)``, jitted; ``t`` counts from 1; the norms are
    ``bench.compare.leaf_norms`` of the gradient."""
    from bench.compare import leaf_norms

    def step(params, m, v, t, tokens, targets):
        loss, grad = loss_and_grad(c, precision, params, tokens, targets)
        out = jax.tree.map(lambda p, g, mm_, vv: adamw(opt, t, p, g, mm_, vv),
                           params, grad, m, v)
        is_t = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_t)
        return pick(0), pick(1), pick(2), loss, leaf_norms(grad)
    return jax.jit(step, donate_argnums=(1, 2))
