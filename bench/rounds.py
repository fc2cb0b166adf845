"""The decode rounds of a traced run, each with the device programs it
ran: what the per-round readers in ``bench/metrics/`` share.

A round is one call of the executor's ``decode`` (``Log.calls``, harness
clock), made while the profiler ran. Each decode program (``jit_decode``)
of the trace belongs to the round whose interval, put on the trace's
clock (``Reduced.ns``), holds the middle of the program: a program starts
after its dispatch and ends before the round returns, so its middle lies
well inside its round even where the host plane and the device plane of
a trace disagree by a dispatch's length.
"""
from __future__ import annotations

import bisect

DECODE_PROGRAM = "jit_decode"


def decode_rounds(run) -> list:
    """Per traced decode round: ``(call, durations)``, the harness's call
    record and the lengths in ms of the decode programs it ran. Empty
    without a trace, or where no decode program lies in a traced
    round."""
    if run.trace is None:
        return []
    mods = sorted(((s + e) / 2, (e - s) / 1e6) for s, e, n in run.trace.modules
                  if n == DECODE_PROGRAM or n.startswith(DECODE_PROGRAM + "("))
    mids = [m for m, _ in mods]
    out = []
    for c in run.traced_calls("decode"):
        i = bisect.bisect_left(mids, run.trace.ns(c[1]))
        j = bisect.bisect_left(mids, run.trace.ns(c[2]))
        out.append((c, [d for _, d in mods[i:j]]))
    if not any(d for _, d in out):
        return []
    return out
