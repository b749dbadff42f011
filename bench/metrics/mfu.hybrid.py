"""Model FLOP utilization of the interleaved hybrid's step over the
traced steps: model FLOPs per token (``bench.counts_hybrid
.hybrid_train_flops_per_token``: the projections and MLPs, the head over
the vocabulary held, causal attention and the SSD cores; recomputation
not counted) times the tokens of the traced steps, over the seconds in
which the chip ran an operation (mean over the chips) times chips times
the bf16 peak."""
from bench import counts_hybrid


def read(r):
    if not r.counts.get("traced_tokens") or r.summary is None:
        return None
    busy = r.summary["mean"]["busy_s"]
    if busy <= 0:
        return None
    flops = counts_hybrid.hybrid_train_flops_per_token(
        r.config, r.cell["traffic_params"]["seq_len"])
    return 100.0 * r.counts["traced_tokens"] * flops / (
        busy * r.chips * r.peaks["bf16_flops_per_s"])
