"""Prefill call time on the host clock, median over the window's calls
(around the executor's prefill, which blocks until the token is ready)."""
from bench.harness import percentile


def read(run):
    return percentile([c[2] - c[1] for c in run.log.calls
                       if c[0] == "prefill"], 50)
