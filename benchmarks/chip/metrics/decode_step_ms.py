"""Device milliseconds of the engine's decode-step program per step."""


def read(run):
    dec = [s["decode_s"] for s in (run.trace or {}).get("steps", [])
           if s["decode_s"] > 0]
    return 1e3 * sum(dec) / len(dec) if dec else None
