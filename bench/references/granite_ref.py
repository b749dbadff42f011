"""granite-4.0-h-micro trained with AdamW, written plainly in float32.

Follows the published architecture (``GraniteMoeHybridForCausalLM`` with
no experts, ``config.json`` of ibm-granite/granite-4.0-h-micro): token
embedding times ``embedding_multiplier``; per layer, as ``layer_types``
gives it, RMSNorm and a mixer, its output times ``residual_multiplier``
added to the residual, then RMSNorm and the SwiGLU MLP (silu(x·W_gate) ⊙
x·W_up)·W_down, times ``residual_multiplier``, added; a final RMSNorm,
the tied head, the logits divided by ``logits_scaling``; mean next-token
cross-entropy.

The mixers:

* Mamba-2 (one group): x, z, B, C and Δ projected from the input; x, B
  and C each through a causal depthwise convolution of width
  ``mamba_d_conv`` with its bias, then SiLU; Δ = softplus(Δ + dt_bias);
  per head, with A = −exp(A_log), the exact recurrence over time steps
  h_t = exp(Δ_t A) h_{t−1} + Δ_t B_t x_tᵀ, y_t = C_t h_t + D x_t;
  y ⊙ SiLU(z) through RMSNorm with its scale; the out-projection.
* Attention: grouped-query causal attention (query head h reads key/value
  head h // (H/KV)) with no positional embedding, the scores times
  ``attention_multiplier``, softmax in float32, output projection.

Departures, for memory only: the batch's rows are taken one at a time and
their gradients averaged; each layer is recomputed in the backward pass;
the recurrence runs in blocks of steps, and the attention over blocks of
queries, each recomputed in the backward pass. None changes a value.

``precision="highest"`` is the reference: float32 matrix products at full
precision. ``"fp8"`` is the control: the operands of every matrix product
of the projections, the attention and the head rounded to float8 e4m3 with
one scale per tensor, the step below the bfloat16 the configuration
computes in (the recurrence stays in float32). ``carry=False`` plants a
fault: the recurrence's state is reset to zero at the start of every
chunk of ``mamba_chunk_size`` steps, so none crosses a chunk boundary.

The weights are the tree ``bench/drivers/hybrid_train.py`` makes
(``layers/<kind>/{ln1, mixer, ln2, mlp}``, each kind's layers stacked in
order on a leading axis) as ``unstack`` lays them out: one dict a layer,
in ``layer_types`` order, so that no layer's gradient is a slice of a
stack.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0
# steps of the recurrence and queries of the attention recomputed as one
# block in the backward pass: what a block keeps for its backward fits
_STEPS = 64
_Q_BLOCK = 256


def _fp8(x):
    """x rounded to float8 e4m3 under one scale for the tensor, in the
    forward pass alone (gradients pass the rounding unchanged)."""
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


def _conv(x, w, b):
    """Causal depthwise convolution: out[t] = b + Σ_k w[k] · x[t−W+1+k],
    zeros before the first step. x (S, C), w (W, C)."""
    width, s = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return b + sum(xp[k:k + s] * w[k] for k in range(width))


def _recurrence(x, dt, a, bm, cm, chunk: int, carry: bool):
    """y_t = C_t h_t with h_t = exp(Δ_t A) h_{t−1} + Δ_t B_t x_tᵀ, step by
    step. x (S, H, P), dt (S, H), a (H,), bm/cm (S, N) → y (S, H, P).
    Blocks of up to ``_STEPS`` steps (dividing ``chunk``), each recomputed
    in the backward pass; with ``carry`` False the state is zero at the
    start of every ``chunk`` steps."""
    s, h, p = x.shape
    n = bm.shape[1]
    block = next(b for b in range(min(_STEPS, chunk), 0, -1)
                 if chunk % b == 0)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + dtt[:, None, None] * bt[None, :, None] * xt[:, None, :])
        return state, jnp.einsum("n,hnp->hp", ct, state, precision=_HIGHEST)

    @jax.checkpoint
    def run_block(state, inp):
        first, steps = inp
        if not carry:
            state = jnp.where(first % chunk == 0, 0.0, state)
        return jax.lax.scan(step, state, steps)

    pad = (-s) % block
    nb = (s + pad) // block
    blocks = [jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)).reshape(
        (nb, block) + t.shape[1:]) for t in (x, dt, bm, cm)]
    _, y = jax.lax.scan(run_block, jnp.zeros((h, n, p), jnp.float32),
                        (jnp.arange(nb) * block, tuple(blocks)))
    return y.reshape(nb * block, h, p)[:s]


def _mamba(c: dict, mm, h, p, carry: bool):
    """The Mamba-2 mixer on one row: h (S, D) → (S, D)."""
    heads, hp = c["mamba_n_heads"], c["mamba_d_head"]
    s = h.shape[0]
    xi = mm("sd,de->se", h, p["in_x"])
    z = mm("sd,de->se", h, p["in_z"])
    bm = mm("sd,dn->sn", h, p["in_b"])
    cm = mm("sd,dn->sn", h, p["in_c"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, p["in_dt"]) + p["dt_bias"])
    xi = jax.nn.silu(_conv(xi, p["conv_x_w"], p["conv_x_b"]))
    bm = jax.nn.silu(_conv(bm, p["conv_b_w"], p["conv_b_b"]))
    cm = jax.nn.silu(_conv(cm, p["conv_c_w"], p["conv_c_b"]))
    xh = xi.reshape(s, heads, hp)
    y = _recurrence(xh, dt, -jnp.exp(p["a_log"]), bm, cm,
                    c["mamba_chunk_size"], carry)
    y = (y + p["d_skip"][None, :, None] * xh).reshape(s, heads * hp)
    y = _rms(y * jax.nn.silu(z), p["gate_norm"], c["rms_norm_eps"])
    return mm("se,ed->sd", y, p["out"])


def _attention(c: dict, mm, h, p):
    """NoPE GQA causal attention on one row, over blocks of queries."""
    s = h.shape[0]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // heads
    q = mm("sd,dhk->shk", h, p["wq"]).reshape(s, kv, heads // kv, hd)
    k = mm("sd,dhk->shk", h, p["wk"])
    v = mm("sd,dhk->shk", h, p["wv"])
    qb = min(_Q_BLOCK, s)
    pad = (-s) % qb
    qs = jnp.pad(q, [(0, pad), (0, 0), (0, 0), (0, 0)]).reshape(
        -1, qb, kv, heads // kv, hd)

    @jax.checkpoint
    def block(_, inp):
        qc, i = inp
        scores = mm("skgd,tkd->kgst", qc, k) * c["attention_multiplier"]
        rows = i * qb + jnp.arange(qb)[:, None]
        causal = jnp.arange(s)[None, :] <= rows
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return None, mm("kgst,tkd->skgd", probs, v)

    _, o = jax.lax.scan(block, None, (qs, jnp.arange(qs.shape[0])))
    o = o.reshape(-1, heads, hd)[:s]
    return mm("shk,hkd->sd", o, p["wo"])


def _layer(c: dict, mm, kind: str, carry: bool, x, lp):
    eps, rm = c["rms_norm_eps"], c["residual_multiplier"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    if kind == "mamba":
        out = _mamba(c, mm, h, lp["mixer"], carry)
    else:
        out = _attention(c, mm, h, lp["mixer"])
    x = x + rm * out
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = mm("sd,df->sf", h, m["w_gate"])
    u = mm("sd,df->sf", h, m["w_up"])
    return x + rm * mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"])


def unstack(params, kinds):
    """The driver's weights (each kind's layers stacked in order) as the
    reference holds them: ``layers`` a list, one dict a layer, in
    ``kinds`` order."""
    seen = {}
    layers = []
    for kind in kinds:
        i = seen[kind] = seen.get(kind, -1) + 1
        layers.append(jax.tree.map(lambda w: w[i], params["layers"][kind]))
    return {**params, "layers": layers}


def stack(tree, kinds):
    """``unstack``'s inverse, for a tree of the same layout (a gradient,
    or a norm a leaf)."""
    by_kind = {}
    for kind, lp in zip(kinds, tree["layers"]):
        by_kind.setdefault(kind, []).append(lp)
    return {**tree, "layers": {
        kind: jax.tree.map(lambda *ws: jnp.stack(ws), *lps)
        for kind, lps in by_kind.items()}}


def leaf_norms(tree, kinds):
    """The float32 L2 norm of each leaf of each layer, in the order
    ``bench.compare.leaf_norms`` gives the driver's stacked layout, made
    without stacking the tree itself."""
    norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)
    return jnp.concatenate([jnp.atleast_1d(n) for n in
                            jax.tree.leaves(stack(norms, kinds))])


def row_loss(c: dict, precision: str, carry: bool, params, tokens, targets):
    """Mean next-token NLL of one row (S,); ``params`` as ``unstack``
    gives them."""
    mm = _mm(precision)
    emb = params["embed"]["embedding"]
    x = emb[tokens] * c["embedding_multiplier"]
    for kind, lp in zip(c["layer_types"], params["layers"]):
        x = jax.checkpoint(functools.partial(_layer, c, mm, kind, carry))(
            x, lp)
    x = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    logits = mm("sd,vd->sv", x, emb) / c["logits_scaling"]
    nll = jax.nn.logsumexp(logits, -1) \
        - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.mean(nll)


def loss_and_grad(c: dict, precision: str, carry: bool, params, tokens,
                  targets):
    """Batch loss and gradient: rows one at a time, averaged (a batch of
    one row holds no second gradient to sum into)."""
    vg = jax.value_and_grad(functools.partial(row_loss, c, precision, carry))
    if tokens.shape[0] == 1:
        return vg(params, tokens[0], targets[0])

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


def make_step(c: dict, opt: dict, precision: str, carry: bool = True):
    """``step(params, m, v, t, tokens, targets) → (params, m, v, loss,
    gradient norms)``, jitted, the weights and moments (as ``unstack``
    gives them) donated; ``t`` counts from 1; the norms are in
    ``bench.compare.leaf_norms``' order (``leaf_norms``)."""

    def step(params, m, v, t, tokens, targets):
        loss, grad = loss_and_grad(c, precision, carry, params, tokens,
                                   targets)
        out = jax.tree.map(lambda p, g, mm_, vv: adamw(opt, t, p, g, mm_, vv),
                           params, grad, m, v)
        is_t = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_t)
        return (pick(0), pick(1), pick(2), loss,
                leaf_norms(grad, c["layer_types"]))
    return jax.jit(step, donate_argnums=(0, 1, 2))
