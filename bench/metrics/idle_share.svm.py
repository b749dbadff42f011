"""Share of the traced stretch in which a chip runs no operation: one
minus the union of its operations' intervals. Mean over the chips."""


def read(r):
    if r.summary is None:
        return None
    return 100.0 * r.summary["mean"]["idle_s"] / r.summary["window_s"]
