"""Decode program's share of its roofline: per decode round of the
executor, the least time the chip could take (the larger of its
operations over the bf16 peak and its bytes over the memory bandwidth,
bench/work.py: the weights once per round), summed over the traced
window's rounds and divided by the device time of every ``jit_decode``
program in it, however many a round runs. No Pallas kernel is on the
decode path, so the kernel here is the whole program."""


def read(run):
    calls = run.traced_calls("decode")
    t, n = run.trace.module_s("jit_decode")
    if not calls or not t or n < len(calls):
        return None
    flops_s, bytes_s = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    roof = 0.0
    for c in calls:
        f, b = run.counts.decode(c[4])
        roof += max(f / flops_s, b / bytes_s)
    return 100.0 * roof / t
