"""Device microseconds a block: the seconds a chip spends under the
program's ``svm.block`` scope (a worker's local update from its next
rows, mean over the chips), over the blocks the traced jobs run on each
worker (``bench.counts.svm_job_counts``)."""


def read(r):
    scopes = (r.summary or {}).get("scopes", {})
    if "svm.block" not in scopes or not r.counts.get("traced_blocks"):
        return None
    return 1e6 * scopes["svm.block"] / r.counts["traced_blocks"]
