"""Milliseconds per engine step in which the device was idle inside the
harness's ``engine.step`` span: the host's share of a step."""


def read(run):
    steps = run.trace["steps"] if run.trace else []
    if not steps:
        return None
    idle = sum((s["t1"] - s["t0"]) / 1e9 - s["busy_s"] for s in steps)
    return 1e3 * idle / len(steps)
