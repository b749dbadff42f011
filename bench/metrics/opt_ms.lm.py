"""Device milliseconds a step under the program's ``lm.optimizer`` scope
(the optimizer's update of weights and moments), mean over the chips,
over the traced steps."""


def read(r):
    scopes = (r.summary or {}).get("scopes", {})
    if "lm.optimizer" not in scopes or not r.counts.get("traced_steps"):
        return None
    return 1e3 * scopes["lm.optimizer"] / r.counts["traced_steps"]
