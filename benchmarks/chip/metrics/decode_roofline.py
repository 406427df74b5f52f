"""Share of its roofline that the decode step reaches: the least time
its bytes and operations need on this chip (the architecture's
``ModelCosts``),
summed over the window's steps, over the decode program's device time
in those steps."""
import timing


def read(run):
    if not run.trace or not run.peak:
        return None
    steps = timing.window_steps(run)
    traced = run.trace["steps"]
    if len(steps) != len(traced):
        raise RuntimeError(f"{len(steps)} steps recorded, {len(traced)} "
                           f"traced")
    bound = dev = 0.0
    for s, t in zip(steps, traced):
        if t["decode_s"] > 0 and s.contexts:
            bound += run.model.decode_seconds_bound(s.contexts, run.peak)
            dev += t["decode_s"]
    return 100.0 * bound / dev if dev else None
