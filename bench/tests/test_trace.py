"""The trace reduction: interval arithmetic on events made by hand, and a
trace recorded on the chip and checked in."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def test_union_complement_overlap():
    u = trace.union([(5, 7, "a"), (0, 2, "b"), (1, 3, "c"), (7, 8, "d")])
    assert u == [(0, 3), (5, 8)]
    assert trace.complement(u, 0, 10) == [(3, 5), (8, 10)]
    assert trace.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace.overlap(u, [(2, 6)]) == 2


def test_idle_is_attributed_to_the_innermost_span():
    spans = [(0, 10, "engine.step"), (2, 8, "executor.decode"),
             (10, 20, "wait.arrival")]
    pieces = trace.innermost(spans, 0, 25)
    assert pieces == [(0, 2, "engine.step"), (2, 8, "executor.decode"),
                      (8, 10, "engine.step"), (10, 20, "wait.arrival"),
                      (20, 25, "none")]
    ev = trace.Events(ops={"/device:TPU:0": [
        (3, 9, "%while.1 = (s32[]) while(%tuple.2)"),
        (3, 6, "%fusion.1 = bf16[4] fusion(%p.1)"),
        (6, 7, "%fusion.2 = f32[2] fusion(%p.2)"),
        (12, 14, "%fusion.1 = bf16[4] fusion(%p.1)")]},
        modules=[(3, 9, "jit_decode(5)"), (12, 14, "jit_prefill")],
        spans=spans, window=(0, 25))
    r = trace.reduce(ev, 100.0, 100.0 + 25e-6)
    assert r.busy_s == 8e-9 and r.window_s == 25e-9
    assert r.module_s("jit_decode") == (6e-9, 1)
    assert r.idle_by_span == {"engine.step": 3, "executor.decode": 1,
                              "wait.arrival": 8, "none": 5}
    # self time: the while loop less the two fusions nested in it
    assert r.breakdown()["device_ops"] == [
        ["%fusion.1", 5e-9], ["%while.1", 2e-9], ["%fusion.2", 1e-9]]
    # a request in the system from 1 to 11 ns: idle 1-3 and 9-11
    assert r.idle_share([(100.0 + 1e-6, 100.0 + 11e-6)]) == pytest.approx(4 / 25)


def recorded():
    """A trace recorded on a TPU v5 lite: the first 30 ms of a traced
    window of the chat cell (one prefill, then decode calls of one
    sequence), cut to that length, with the harness's executor calls."""
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "decode.xplane.pb.gz").read_bytes())
    log = json.loads((DATA / "decode.json").read_text())
    return trace.events(ProfileData.from_serialized_xspace(raw)), log


def test_recorded_trace_reduces_to_the_harness_calls():
    ev, log = recorded()
    assert list(ev.ops) == ["/device:TPU:0"] and ev.window is not None
    r = trace.reduce(ev, log["on_ms"], log["off_ms"])
    calls = [c for c in log["calls"]
             if log["on_ms"] <= c[1] and c[2] <= log["off_ms"]]
    for kind in ("prefill", "decode"):
        t, n = r.module_s("jit_" + kind)
        assert n == sum(len(c[3]) for c in calls if c[0] == kind) > 0
        assert 0 < t
    t_all = sum(r.module_s("jit_" + k)[0] for k in ("prefill", "decode"))
    assert t_all <= r.busy_s <= r.window_s
    # busy and idle partition the window; idle is named after the
    # harness's spans
    idle = sum(r.idle_by_span.values()) / 1e9
    assert idle + r.busy_s == pytest.approx(r.window_s, rel=1e-9)
    assert set(r.idle_by_span) <= {"none", "wait.arrival", "wait.event",
                                   "engine.arrive", "engine.step",
                                   "engine.deliver", "executor.prefill",
                                   "executor.decode"}
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= trace.TOP
    assert all(n.startswith("%") and " " not in n for n, _ in b["device_ops"])
    # self times add up to no more than the time the chip was busy
    assert sum(r.op_time.values()) / 1e9 <= r.busy_s * (1 + 1e-9)
