"""Gap between a request's consecutive tokens, 99th percentile over every
gap that ends in the window."""
from bench.harness import itl_ms, percentile


def read(run):
    return percentile(itl_ms(run), 99)
