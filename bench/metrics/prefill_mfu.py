"""Prefill's share of the chip's peak: the operations the traced window's
prefills require (bench/work.py) over the device time of the prefill
program (``jit_prefill``) times the bf16 peak."""


def read(run):
    calls = run.traced_calls("prefill")
    t, n = run.trace.module_s("jit_prefill")
    if not calls or not t or n != len(calls):
        return None
    flops = sum(run.counts.prefill(p)[0] for c in calls for p in c[4])
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
