"""Gap between a request's consecutive tokens, median over every gap that
ends in the window, by the wall time at which the executor returned each
token."""
from bench.harness import itl_ms, percentile


def read(run):
    return percentile(itl_ms(run), 50)
