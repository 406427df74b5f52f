"""Output tokens served inside the window over the window's length."""


def read(run):
    n = sum(1 for r in run.requests.values() for t in r.times
            if run.t_open < t <= run.t_close)
    return n / run.seconds
