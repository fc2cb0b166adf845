"""Device-pool specialization for serving (the TPU adaptation: prefill
and decode on separate device pools): interference and its mitigation,
asymmetric-rule invariants — through the repro.sched Policy/Topology
API."""
import copy

import pytest

from repro.sched import SharedBaselinePolicy, SpecializedPolicy, Topology
from repro.sched.engine import Engine, PoolModel, ServeConfig
from repro.sched.workload import poisson_workload

PM = PoolModel(prefill_ms_per_ktok=320.0, decode_fixed_ms=760.0,
               decode_ms_per_seq=24.0, handoff_ms=2.0)


def _run(spec, wl, n_dev=16, pre_dev=4, horizon=60_000.0):
    if spec:
        topo, pol = Topology.serving(n_dev, pre_dev), SpecializedPolicy()
    else:
        topo, pol = Topology.shared(n_dev), SharedBaselinePolicy()
    return Engine(topo, pol, PM).run(copy.deepcopy(wl), horizon)


@pytest.fixture(scope="module")
def workload():
    return poisson_workload(3.2, 60_000, prompt_len=2048, max_new=64, seed=5)


def test_specialization_cuts_itl_tail_spread(workload):
    ns = _run(False, workload).summary()
    sp = _run(True, workload).summary()
    spread_ns = ns["itl_p99_ms"] - ns["itl_p50_ms"]
    spread_sp = sp["itl_p99_ms"] - sp["itl_p50_ms"]
    assert spread_sp < 0.5 * spread_ns, (ns, sp)


def test_handoffs_happen_only_with_specialization(workload):
    ns = _run(False, workload)
    sp = _run(True, workload)
    assert ns.steals == 0 and ns.handoffs == 0
    assert sp.handoffs > 0


def test_decode_pool_never_prefills(workload):
    """With specialization the decode pool accumulates zero prefill
    (heavy) busy time, and TTFT >= pure prefill service time."""
    m = _run(True, workload)
    assert m.pool_busy["decode"]["heavy"] == 0.0
    min_prefill_ms = PM.prefill_ms(1024, 4)   # smallest possible prompt
    assert min(m.ttft_ms) >= min_prefill_ms * 0.99


def test_throughput_not_sacrificed(workload):
    ns = _run(False, workload).summary()
    sp = _run(True, workload).summary()
    assert sp["throughput_tok_s"] >= 0.85 * ns["throughput_tok_s"]


def test_overload_keeps_requests_on_prefill_pool():
    """Asymmetric stealing: when the decode pool saturates but prefill has
    idle gaps, freshly prefilled requests decode on the prefill pool."""
    wl = poisson_workload(4.0, 20_000, prompt_len=512, max_new=512, seed=1)
    eng = Engine(Topology.serving(8, 2), SpecializedPolicy(), PM,
                 ServeConfig(decode_batch_max=16))
    m = eng.run(wl, 20_000)
    assert m.steals > 0


def test_handoffs_counted_once_per_transfer(workload):
    """Every handoff is one actual pool transfer: with no overload (large
    decode_batch_max) each completed-or-inflight prefill hands off exactly
    once, so handoffs == number of requests that finished prefill."""
    m = _run(True, workload)
    assert m.handoffs == len(m.ttft_ms)


def test_edf_deadlines_assigned_and_ordered():
    """The engine schedules EDF by arrive_ms + deadline_window_ms (the
    MuQSS virtual-deadline analogue), not bare FIFO: every admitted
    request carries its deadline, and first tokens are produced in
    deadline order when the window is uniform."""
    cfg = ServeConfig(deadline_window_ms=50.0)
    reqs = poisson_workload(2.0, 10_000, prompt_len=1024, max_new=4, seed=7)
    m = Engine(Topology.serving(4, 2), SpecializedPolicy(), PM, cfg).run(
        reqs, 60_000)
    assert m.completed > 0
    admitted = [r for r in reqs if r.deadline > 0]
    assert admitted
    for r in admitted:
        assert r.deadline == pytest.approx(r.arrive_ms + 50.0)
    finished = [r for r in admitted if r.ttft_ms is not None]
    by_deadline = sorted(finished, key=lambda r: r.deadline)
    ttfts = [r.arrive_ms + r.ttft_ms for r in by_deadline]
    assert ttfts == sorted(ttfts)
