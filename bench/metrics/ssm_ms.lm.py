"""Device milliseconds a step under the program's ``lm.ssm`` scope and
the ``lm.ssd`` scope nested in it (the Mamba-2 mixer whole: projections,
convolutions, the SSD scan, gated norm and out-projection; forward, remat
recompute and backward), mean over the chips, over the traced steps."""


def read(r):
    scopes = (r.summary or {}).get("scopes", {})
    if "lm.ssm" not in scopes or not r.counts.get("traced_steps"):
        return None
    return 1e3 * (scopes["lm.ssm"] + scopes.get("lm.ssd", 0.0)) \
        / r.counts["traced_steps"]
