"""Process start to ready to measure (host clock): imports, weights,
plan tuning, compiles or compile-cache loads, and the warm-up stretch of
the cell's own traffic."""


def read(run):
    return run.setup_s
