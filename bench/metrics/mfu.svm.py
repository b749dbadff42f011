"""The SVM step's share of four chips' roofline over the traced jobs:
the larger of its FLOP share and its byte share (the byte share binds).
Work per sample from ``bench.counts``: 4·(d+1) bytes, 4·d FLOPs."""
from bench import counts


def read(r):
    if not r.counts.get("traced_samples") or r.summary is None:
        return None
    rate = r.counts["traced_samples"] / r.summary["window_s"]
    d = r.config["features"]
    flops = rate * counts.svm_flops_per_sample(d) \
        / (r.chips * r.peaks["bf16_flops_per_s"])
    hbm = rate * counts.svm_bytes_per_sample(d) \
        / (r.chips * r.peaks["hbm_bytes_per_s"])
    return 100.0 * max(flops, hbm)
