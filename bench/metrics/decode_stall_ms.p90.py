"""How long a sequence ready for its next token waited for the decode
round that advanced it, 90th percentile over every sequence of every
round (one sample per token, as the gaps between tokens are counted).

A sequence is ready when its previous token came back, or, after its
prefill, once the engine's KV handoff (``PoolModel.handoff_ms``) is
done; its wait ends when the round's executor call starts (harness
clock). Under the harness one host thread makes one call at a time, so
a sequence ready during another pool's prefill waits it out. Only waits
that begin and end in the traced part of the window count: stopping the
profiler holds the loop for seconds. Without a trace, the whole window.
"""
import bisect

from bench.harness import percentile
from repro.sched.engine import PoolModel


def read(run):
    lo, hi = (0.0, run.window_ms) if run.trace is None \
        else (run.trace_on_ms, run.trace_off_ms)
    handoff = PoolModel().handoff_ms
    waits = []
    for kind, start, end, rids, _ in run.log.calls:
        if kind != "decode" or not lo <= start < hi:
            continue
        for rid in rids:
            toks = run.log.tokens[rid]
            k = bisect.bisect_left(toks, end)
            if k == 0:
                continue
            ready = toks[k - 1] + (handoff if k == 1 else 0.0)
            if ready >= lo:
                waits.append(start - ready)
    return percentile(waits, 90)
