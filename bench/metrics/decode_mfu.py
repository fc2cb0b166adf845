"""Decode's share of the chip's peak: the operations the traced window's
decode rounds require (bench/work.py) over the device time of every
decode program (``jit_decode``) in it times the bf16 peak."""


def read(run):
    calls = run.traced_calls("decode")
    t, n = run.trace.module_s("jit_decode")
    if not calls or not t or n < len(calls):
        return None
    flops = sum(run.counts.decode(c[4])[0] for c in calls)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
