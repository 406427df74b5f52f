"""90th percentile of time to first token over every request due in the
window, timed from its due time."""
import timing


def read(run):
    return timing.percentile_ms(timing.ttft_s(run), 90)
