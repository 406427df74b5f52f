"""95th percentile over requests of each request's mean time per output
token after its first: every stall a request met is averaged into it."""
import timing


def read(run):
    return timing.percentile_ms(timing.tpot_s(run), 95)
