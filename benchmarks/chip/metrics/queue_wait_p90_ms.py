"""90th percentile of the scheduler's queue wait, ``t_admitted`` minus
``t_queued`` (the engine's stamps on each ``Request``, on the host's
clock), over the requests admitted inside the window."""
import math

import timing


def read(run):
    waits = [r.t_admitted - r.t_queued for r in run.requests.values()
             if run.t_open <= getattr(r, "t_admitted", math.nan)
             < run.t_close]
    return timing.percentile_ms(waits, 90)
