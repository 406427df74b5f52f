"""Per-request latency arithmetic over a run's window, on the host's
clock. A token's time is the end of the engine step that served it: the
engine hands tokens back only when ``step()`` returns."""
from __future__ import annotations

from typing import List

import numpy as np


def due_in_window(run) -> List:
    """Requests due while the window was open and not refused."""
    return [r for r in run.requests.values()
            if run.t_open <= r.due < run.t_close and not r.rejected]


def ttft_s(run) -> List[float]:
    """First token time minus due time; a request still without its
    first token when the window closes counts the close minus its due."""
    out = []
    for r in due_in_window(run):
        t = r.times[0] if r.times and r.times[0] <= run.t_close \
            else run.t_close
        out.append(t - r.due)
    return out


def tpot_s(run) -> List[float]:
    """Per request, (last - first token time) / (tokens - 1), over the
    tokens served by the close; a request still running counts the gaps
    it has had. Requests with fewer than two tokens have no gap."""
    out = []
    for r in due_in_window(run):
        ts = [t for t in r.times if t <= run.t_close]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def percentile_ms(values: List[float], q: float):
    """The ``q``-th percentile (linear interpolation) in ms, or None."""
    if not values:
        return None
    return 1e3 * float(np.percentile(np.asarray(values, np.float64), q))


def window_steps(run) -> List:
    """Engine steps of the window's loop (the last may end after the
    close)."""
    return [s for s in run.steps if s.t0 >= run.t_open]
