"""Host milliseconds a step in the runner's ``repro.dispatch`` span (the
call that hands the step to the device), less what other ``repro.``
spans inside it cover, over the traced steps."""


def read(r):
    spans = (r.summary or {}).get("host_spans", {})
    if "dispatch" not in spans or not r.counts.get("traced_steps"):
        return None
    return 1e3 * spans["dispatch"] / r.counts["traced_steps"]
