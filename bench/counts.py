"""Work a step needs, counted from shapes: the yardstick behind every
utilization and roofline share the benchmark reports."""
from __future__ import annotations


def lm_param_count(c: dict) -> int:
    """All parameters of a llama-style decoder with tied embeddings."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd, h, kv = c["head_dim"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    attn = d * h * hd * 2 + d * kv * hd * 2          # wq, wo, wk, wv
    layer = attn + 3 * d * f + 2 * d                 # + SwiGLU + 2 norms
    head = 0 if c["tie_word_embeddings"] else v * d
    return c["num_hidden_layers"] * layer + v * d + head + d


def lm_matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the layers' projections and the output head (the tied embedding
    counts once, as the head; the lookup is a gather)."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd, h, kv = c["head_dim"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return c["num_hidden_layers"] * layer + v * d


def lm_train_flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per
    matmul parameter, plus causal attention's score and value products,
    6·L·S·H·hd (half of the full square). Recomputation is not counted."""
    attn = 6 * c["num_hidden_layers"] * seq_len \
        * c["num_attention_heads"] * c["head_dim"]
    return 6.0 * lm_matmul_params(c) + attn


def svm_bytes_per_sample(d: int) -> int:
    """One float32 row of x and its label, read once per epoch."""
    return 4 * (d + 1)


def svm_flops_per_sample(d: int) -> int:
    """The margin's dot product and the row's share of the block
    gradient: 2·d each."""
    return 4 * d


def svm_job_counts(n_local: int, block_size: int, epochs: int,
                   overlap: str = "none", topology: str = "all") -> dict:
    """What one DMS job does on each worker: ``blocks`` local block
    updates (the rows past the last whole block are left out), and
    ``syncs`` exchanges under the program's ``svm.sync`` scope, one a
    block plus the flush's mean wherever the carry is not already the
    synchronized model (any overlap, or a gossip topology)."""
    blocks = epochs * (n_local // block_size)
    flush = int(overlap != "none" or topology != "all")
    return {"blocks": blocks, "syncs": blocks + flush}
