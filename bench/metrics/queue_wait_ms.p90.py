"""Engine queue wait, 90th percentile over the requests due in the traced
part of the window: from the due time to the start of the request's
prefill call, or to the end of the traced part where none had started.

Stopping the profiler holds the harness's loop for seconds (12.4 s on a
TPU v5e serving the starcoder2 stage), so requests due after the traced
part wait through it; they are left out. Without a trace, the whole window."""
from bench.harness import percentile


def read(run):
    lo, hi = (0.0, run.window_ms) if run.trace is None \
        else (run.trace_on_ms, run.trace_off_ms)
    start = run.log.prefill_start
    return percentile([min(start.get(rid, hi), hi) - due
                       for rid, due in run.due.items() if lo <= due < hi], 90)
