"""Device microseconds a sync: the seconds a chip spends under the
program's ``svm.sync`` scope (the exchange of the model after each
block, mean over the chips), over the exchanges the traced jobs make on
each worker (``bench.counts.svm_job_counts``)."""


def read(r):
    scopes = (r.summary or {}).get("scopes", {})
    if "svm.sync" not in scopes or not r.counts.get("traced_syncs"):
        return None
    return 1e6 * scopes["svm.sync"] / r.counts["traced_syncs"]
