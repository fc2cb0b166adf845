"""Time to first token, 90th percentile over every request due in the
window: from its due time to the return of the executor call that gave
its first token. A request with no token by the window's end enters with
its wait so far."""
from bench.harness import percentile


def read(run):
    w = run.window_ms
    vals = []
    for rid, due in run.due.items():
        toks = run.log.tokens.get(rid)
        first = toks[0] if toks and toks[0] <= w else w
        vals.append(first - due)
    return percentile(vals, 90)
