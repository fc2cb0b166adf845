"""The readers of decode rounds (``decode_calls_per_round.mean``,
``decode_host_ms.p50``, ``decode_stall_ms.p90``) on runs built by hand
and on a trace recorded on the chip, and the executor's annotations in
a profiler trace of a small engine run on the CPU."""
from collections import Counter

import jax
import numpy as np
import pytest

from bench import harness, trace
from bench.tests.test_trace import recorded
from conftest import ROOT
from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.launch.serve import RealModelExecutor
from repro.models.api import build_model
from repro.sched import SpecializedPolicy, Topology
from repro.sched.engine import Engine, Request, ServeConfig

MS = 1_000_000      # ns per ms: the hand-built traces start at 0 on both clocks


def metric(name):
    return harness.load_metric(ROOT, name).read


def run_of(calls, tokens=None, modules=(), on_ms=0.0, off_ms=30.0,
           traced=True):
    log = harness.Log(annotate=False)
    log.calls = [tuple(c) for c in calls]
    for rid, toks in (tokens or {}).items():
        log.tokens[rid] = list(toks)
    run = harness.Run(cell="c", cfg={}, mix={}, seconds=off_ms / 1e3,
                      due={}, max_new={}, log=log, counts=None, peaks={})
    if traced:
        ev = trace.Events(ops={"/device:TPU:0": [(s, e, "%fusion.1 = f")
                                                 for s, e, _ in modules]},
                          modules=list(modules),
                          window=(on_ms * MS, off_ms * MS))
        run.trace = trace.reduce(ev, on_ms, off_ms)
        run.trace_on_ms, run.trace_off_ms = on_ms, off_ms
    return run


def ns(a, b, name="jit_decode"):
    return (int(a * MS), int(b * MS), name)


# two traced rounds: 2 programs in 4 ms, then 1 in 2 ms, that one
# starting before its round does, as the planes of a process's first
# trace disagree; a prefill; and a round cut by the traced part's end
ROUNDS = [("decode", 1.0, 5.0, (1, 2), (9, 9)),
          ("prefill", 5.5, 6.0, (3,), (8,)),
          ("decode", 6.0, 8.0, (1,), (10,)),
          ("decode", 9.0, 12.0, (1, 2), (11, 10))]
PROGRAMS = [ns(1.2, 2.2), ns(2.2, 3.2), ns(5.6, 5.9, "jit_prefill"),
            ns(5.9, 6.9), ns(9.5, 10.5), ns(10.6, 11.6)]


def test_calls_and_host_time_per_traced_round():
    run = run_of(ROUNDS, modules=PROGRAMS, off_ms=10.0)
    assert metric("decode_calls_per_round.mean")(run) == 1.5
    # 4 - 2 ms and 2 - 1 ms of the host's own time
    assert metric("decode_host_ms.p50")(run) == pytest.approx(1.5)


@pytest.mark.parametrize("case", ["untraced", "no decode program"])
def test_round_readers_read_nothing_without_decode_programs(case):
    run = run_of(ROUNDS, modules=[] if case == "untraced" else PROGRAMS[2:3],
                 off_ms=10.0, traced=case != "untraced")
    for name in ("decode_calls_per_round.mean", "decode_host_ms.p50"):
        assert metric(name)(run) is None


def test_calls_per_round_are_the_sequences_per_call_on_a_chip_trace():
    ev, log = recorded()
    run = run_of(log["calls"], on_ms=log["on_ms"], off_ms=log["off_ms"],
                 traced=False)
    run.trace_on_ms, run.trace_off_ms = log["on_ms"], log["off_ms"]
    run.trace = trace.reduce(ev, log["on_ms"], log["off_ms"])
    calls = run.traced_calls("decode")
    assert calls
    assert metric("decode_calls_per_round.mean")(run) == \
        np.mean([len(c[3]) for c in calls])
    host = metric("decode_host_ms.p50")(run)
    assert 0 < host < np.median([c[2] - c[1] for c in calls])


def test_stall_counts_each_sequence_from_when_it_was_ready():
    # rid 1: prefill token at 10, handoff to 12, decoded 14-20 (waits 2);
    # then nine sequences wait 1 ms and one waits 100 ms
    calls = [("decode", 14.0, 20.0, (1,), (0,)),
             ("decode", 21.0, 30.0, tuple(range(1, 10)), (0,) * 9),
             ("decode", 130.0, 131.0, (10,), (0,)),
             # ready before the traced part, or starting after it
             ("decode", 5.0, 6.0, (11,), (0,)),
             ("decode", 300.0, 301.0, (12,), (0,))]
    tokens = {1: [10.0, 20.0, 30.0], 10: [28.0, 131.0],
              11: [0.5, 6.0], 12: [290.0, 301.0],
              **{r: [1.0, 20.0, 30.0] for r in range(2, 10)}}
    run = run_of(calls, tokens, on_ms=3.0, off_ms=200.0)
    waits = [2.0] + [1.0] * 9 + [100.0]
    assert metric("decode_stall_ms.p90")(run) == pytest.approx(
        np.percentile(waits, 90))
    # one sample per round would read the 100 ms wait as the tail
    assert np.percentile(waits, 90) != np.percentile([2.0, 1.0, 100.0], 90)


def test_executor_annotations_land_on_the_host_plane(tmp_path):
    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = build_model(cfg, no_dist())
    ex = RealModelExecutor(model, jax.jit(model.init)(jax.random.key(0)),
                           cfg.vocab, 8, 11, jax.devices()[0])
    ex.compile()
    eng = Engine(Topology.serving(n_devices=2, prefill_devices=1),
                 SpecializedPolicy(),
                 cfg=ServeConfig(prefill_chunk=8, decode_batch_max=2),
                 executor=ex)
    reqs = [Request(rid=i, arrive_ms=0.0, prompt_len=8, max_new=3)
            for i in range(3)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    ev = trace.load(tmp_path)
    # each is a host event the reduction attributes idle time to
    calls = 3 + 3 * 2
    assert Counter(n for _, _, n in ev.spans) == {
        "executor.upload": 3, "executor.dispatch": calls,
        "executor.sync": calls}
    spans = sorted(ev.spans)
    for (_, e, n), (s, _, m) in zip(spans, spans[1:]):
        assert e <= s
        if n == "executor.dispatch":
            assert m == "executor.sync"
