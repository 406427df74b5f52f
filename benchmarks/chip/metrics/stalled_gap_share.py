"""Share of the inter-token gaps served in the window during which the
engine prefilled another request (the step that ended the gap admitted
someone else)."""
import timing


def read(run):
    total = stalled = 0
    for s in timing.window_steps(run):
        if s.t1 > run.t_close:
            continue
        for rid, n in s.served.items():
            gaps = n - 1 if rid in s.admitted else n
            total += gaps
            if set(s.admitted) - {rid}:
                stalled += gaps
    return 100.0 * stalled / total if total else None
