"""The SSD core's share of its roofline: the least time the chip could
take for the work the traced steps' SSD cores need (``bench.counts_hybrid
.ssd_train_work``: the larger of its FLOPs over the bf16 peak and its
HBM bytes over peak bandwidth, a chip's share of the tokens), over the
device time under the program's ``lm.ssd`` scope (mean over the
chips)."""
from bench import counts_hybrid


def read(r):
    scopes = (r.summary or {}).get("scopes", {})
    steps = r.counts.get("traced_steps")
    if not scopes.get("lm.ssd") or not steps:
        return None
    t = r.cell["traffic_params"]
    flops, nbytes = counts_hybrid.ssd_train_work(
        r.config, steps * t["global_batch"] * t["seq_len"]
        // r.chips)
    least = max(flops / r.peaks["bf16_flops_per_s"],
                nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / scopes["lm.ssd"]
