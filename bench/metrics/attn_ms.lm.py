"""Device milliseconds a step under the program's ``lm.attention`` scope
(projections, RoPE, the attention core and ``wo``: forward, remat
recompute and backward), mean over the chips, over the traced steps."""


def read(r):
    scopes = (r.summary or {}).get("scopes", {})
    if "lm.attention" not in scopes or not r.counts.get("traced_steps"):
        return None
    return 1e3 * scopes["lm.attention"] / r.counts["traced_steps"]
