"""Model FLOP utilization of the LM step over the traced steps: model
FLOPs per token (``bench.counts.lm_train_flops_per_token``, recomputation
not counted) times the tokens of the traced steps, over the seconds in
which the chip ran an operation (mean over the chips) times chips times
the bf16 peak. Host gaps are ``idle_share.lm``'s and ``host_gap_ms.lm``'s
to read, not this one's."""
from bench import counts


def read(r):
    if not r.counts.get("traced_tokens") or r.summary is None:
        return None
    busy = r.summary["mean"]["busy_s"]
    if busy <= 0:
        return None
    flops = counts.lm_train_flops_per_token(
        r.config["config"], r.cell["traffic_params"]["seq_len"])
    return 100.0 * r.counts["traced_tokens"] * flops / (
        busy * r.chips * r.peaks["bf16_flops_per_s"])
