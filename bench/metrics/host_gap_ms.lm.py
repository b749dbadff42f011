"""Milliseconds per step in which the chip is idle while the host runs
the trainer's loop (the feed, the step's dispatch, the runner's wait and
metric fetch). The breakdown names the host span open in each gap."""


def read(r):
    if not r.counts.get("traced_steps") or r.summary is None:
        return None
    return 1000.0 * r.summary["mean"]["idle_s"] / r.counts["traced_steps"]
