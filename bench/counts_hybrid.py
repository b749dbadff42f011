"""Work a step of the interleaved Mamba-2/attention hybrid needs, counted
from a published config's shapes (granite-4.0-h): the yardstick behind
``mfu.hybrid`` and ``ssd_roofline.lm``. The same counts hold whatever
implements a layer, so that no implementation can read over 100%."""
from __future__ import annotations

from typing import Tuple


def _widths(c: dict):
    """(D, F, V, H, KV, hd, SSD heads, P, N) of a config."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d, c["shared_intermediate_size"], c["vocab_size"], h,
            c["num_key_value_heads"], d // h, c["mamba_n_heads"],
            c["mamba_d_head"], c["mamba_d_state"])


def hybrid_param_count(c: dict) -> int:
    """All parameters, with the tied embedding counted once."""
    d, f, v, h, kv, hd, mh, p, n = _widths(c)
    e, w = mh * p, c["mamba_d_conv"]
    mamba = (d * (2 * e + 2 * n + mh) + (w + 1) * (e + 2 * n) + 3 * mh
             + e + e * d)
    attn = 2 * d * h * hd + 2 * d * kv * hd
    layer = 3 * d * f + 2 * d
    kinds = c["layer_types"]
    return (kinds.count("mamba") * (mamba + layer)
            + kinds.count("attention") * (attn + layer) + v * d + d)


def hybrid_matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    every layer's projections and MLP and the output head (the tied
    embedding counts once, as the head; the lookup is a gather). Not the
    depthwise convolutions, the norms or the per-head Δ bias, A and D."""
    d, f, v, h, kv, hd, mh, p, n = _widths(c)
    e = mh * p
    mamba = d * (2 * e + 2 * n + mh) + e * d
    attn = 2 * d * h * hd + 2 * d * kv * hd
    kinds = c["layer_types"]
    return (kinds.count("mamba") * (mamba + 3 * d * f)
            + kinds.count("attention") * (attn + 3 * d * f) + v * d)


def ssd_forward_flops_per_token(c: dict) -> int:
    """The SSD core's forward FLOPs a token and layer (one group):
    2·Q·N for C·Bᵀ inside the chunk, Q·H·P for the causal half of the
    intra-chunk product, 4·H·N·P to read and update the state."""
    _, _, _, _, _, _, mh, p, n = _widths(c)
    q = c["mamba_chunk_size"]
    return 2 * q * n + q * mh * p + 4 * mh * n * p


def ssd_bytes_per_token(c: dict, itemsize: int = 2) -> float:
    """HBM bytes the SSD core needs a token and layer in one pass: x, Δ,
    B and C read and y written in the compute dtype, plus its share of
    the float32 chunk states written once."""
    _, _, _, _, _, _, mh, p, n = _widths(c)
    io = itemsize * (2 * mh * p + mh + 2 * n)
    return io + 4 * mh * n * p / c["mamba_chunk_size"]


def ssd_train_work(c: dict, tokens: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the SSD cores of all Mamba-2 layers need to train on
    ``tokens`` tokens: the backward counts twice the forward's FLOPs,
    and reads and writes as much as the forward (the chunk states once);
    recomputation is not counted."""
    layers = c["layer_types"].count("mamba")
    flops = 3 * ssd_forward_flops_per_token(c)
    _, _, _, _, _, _, mh, p, n = _widths(c)
    states = 4 * mh * n * p / c["mamba_chunk_size"]
    nbytes = 2 * (ssd_bytes_per_token(c) - states) + states
    return layers * tokens * flops, layers * tokens * nbytes


def hybrid_train_flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per
    matmul parameter, 6·S·H·hd for each causal attention layer (half the
    full square, as ``bench.counts.lm_train_flops_per_token`` counts), and
    3 times each SSD core's forward. Recomputation is not counted."""
    d, _, _, h, _, hd, _, _, _ = _widths(c)
    kinds = c["layer_types"]
    attn = 6 * kinds.count("attention") * seq_len * h * hd
    ssd = 3 * kinds.count("mamba") * ssd_forward_flops_per_token(c)
    return 6.0 * hybrid_matmul_params(c) + attn + ssd
