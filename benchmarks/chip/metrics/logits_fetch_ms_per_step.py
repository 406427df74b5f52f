"""Milliseconds per decode step in which the device sat idle inside the
engine's ``serve.fetch`` span: the device-to-host copy of the step's
logits, once the step program has finished (``serve.wait``)."""


def read(run):
    fetch = (run.trace or {}).get("spans", {}).get("serve.fetch")
    if not fetch:
        return None
    return 1e3 * fetch["idle_s"] / fetch["count"]
