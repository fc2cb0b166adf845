"""A decode round's time on the host clock over the sequences it
advanced, median over the window's rounds."""
from bench.harness import percentile


def read(run):
    return percentile([(c[2] - c[1]) / len(c[3]) for c in run.log.calls
                       if c[0] == "decode"], 50)
