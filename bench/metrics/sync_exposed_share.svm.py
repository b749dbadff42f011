"""Share of the traced stretch in which a chip runs a collective and no
other operation: the sync the block's compute does not hide. Mean over
the chips."""


def read(r):
    if r.summary is None:
        return None
    return 100.0 * r.summary["mean"]["exposed_collective_s"] \
        / r.summary["window_s"]
