"""The SVM block's share of its HBM roofline: the bytes the traced jobs
must read (4·(d+1) per sample), over the device time of the program's
non-collective operations, summed over the chips, at peak HBM bandwidth.
"""
from bench import counts


def read(r):
    if not r.counts.get("traced_samples") or r.summary is None:
        return None
    busy = sum(d["other_s"] for d in r.summary["per_device"].values())
    if busy <= 0:
        return None
    need = r.counts["traced_samples"] \
        * counts.svm_bytes_per_sample(r.config["features"])
    return 100.0 * need / busy / r.peaks["hbm_bytes_per_s"]
