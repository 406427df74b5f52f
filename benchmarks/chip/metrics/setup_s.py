"""Set-up seconds: process start to the window's opening (loading,
weights, engine, warm-up, and compiles where the cache misses)."""


def read(run):
    return run.setup_s
