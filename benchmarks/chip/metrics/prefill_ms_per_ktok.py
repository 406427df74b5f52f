"""Device milliseconds of the prefill programs per 1,000 prefilled
(bucket) tokens."""


def read(run):
    pre = sum(s["prefill_s"] for s in (run.trace or {}).get("steps", []))
    if not pre or not run.prefill_tokens:
        return None
    return 1e3 * pre / (run.prefill_tokens / 1000)
