"""Policy/Topology API conformance: the same four policies drive both
mechanisms — the event-driven serving engine (`sched/engine.py`) and
the MuQSS OS simulator (`core/muqss.py` + `core/simulator.py`)."""
import copy

import pytest

from repro.core.muqss import SchedConfig, Scheduler
from repro.core.task import Task, TaskType
from repro.sched import (AdaptivePolicy, CohortPolicy, SharedBaselinePolicy,
                         SpecializedPolicy, Topology, WorkKind)
from repro.sched.engine import Engine, PoolModel, ServeConfig
from repro.sched.workload import poisson_workload

PM = PoolModel(prefill_ms_per_ktok=326.0, decode_fixed_ms=757.0,
               decode_ms_per_seq=23.6, handoff_ms=2.0)


def _workload(seed=3, duration=30_000):
    return poisson_workload(2.0, duration, prompt_len=2048, max_new=64,
                            seed=seed)


def _engine_setup(policy_name):
    return {
        "specialized": (Topology.serving(16, 4), SpecializedPolicy()),
        "shared": (Topology.shared(16), SharedBaselinePolicy()),
        "cohort": (Topology.shared(16), CohortPolicy(batch_n=4)),
        "adaptive": (Topology.serving(16, 4), AdaptivePolicy()),
    }[policy_name]


# ------------------------------------------------------------ topology


def test_topology_partition_validated():
    with pytest.raises(ValueError):
        Topology((Topology.shared(4).pools[0],
                  Topology.shared(4).pools[0]))   # duplicate units
    with pytest.raises(ValueError):
        Topology.split(4, 0)
    with pytest.raises(ValueError):
        Topology.split(4, 4)


def test_topology_lookup_and_resize():
    topo = Topology.serving(16, 4)
    assert topo.n_units == 16
    assert topo.pool("prefill").n_units == 4
    assert topo.pool_of_unit(0).name == "decode"
    assert topo.pool_of_unit(15).name == "prefill"
    assert not topo.pool("decode").can(WorkKind.HEAVY)
    grown = topo.resized("prefill", 6)
    assert grown.pool("prefill").n_units == 6
    assert grown.pool("decode").n_units == 10
    assert grown.n_units == 16


# --------------------------------------------- engine conformance suite


@pytest.mark.parametrize("policy_name",
                         ["specialized", "shared", "cohort", "adaptive"])
def test_engine_completes_under_every_policy(policy_name):
    topo, pol = _engine_setup(policy_name)
    m = Engine(topo, pol, PM).run(_workload(), 30_000)
    s = m.summary()
    assert s["completed"] > 0, (policy_name, s)
    assert s["itl_p50_ms"] > 0
    assert s["ttft_p50_ms"] > 0
    # work conservation: every charged ms belongs to some pool
    busy = sum(v["heavy"] + v["light"] for v in m.pool_busy.values())
    assert busy == pytest.approx(m.prefill_busy_ms + m.decode_busy_ms)


def test_specialized_decode_pool_never_prefills():
    topo, pol = _engine_setup("specialized")
    m = Engine(topo, pol, PM).run(_workload(), 30_000)
    assert m.pool_busy["decode"]["heavy"] == 0.0
    assert m.pool_busy["prefill"]["heavy"] > 0.0


def test_shared_baseline_interleaves():
    """The shared pool runs both kinds (prefill stalls co-located
    decodes — the interference the specialization removes)."""
    topo, pol = _engine_setup("shared")
    m = Engine(topo, pol, PM).run(_workload(), 30_000)
    assert m.pool_busy["shared"]["heavy"] > 0.0
    assert m.pool_busy["shared"]["light"] > 0.0
    assert m.handoffs == 0 and m.steals == 0


def test_cohort_batches_heavy_sections():
    topo, pol = _engine_setup("cohort")
    assert pol.heavy_burst(topo, topo.pool("shared")) == 4
    m = Engine(topo, pol, PM).run(_workload(), 30_000)
    assert m.handoffs == 0                    # still no pool split
    assert m.summary()["completed"] > 0


def test_zero_heavy_burst_does_not_hang():
    """A degenerate policy burst of 0 is clamped to 1: the engine must
    make progress instead of spinning at one simulated instant."""
    m = Engine(Topology.shared(4), CohortPolicy(batch_n=0), PM).run(
        _workload(duration=5_000), 5_000)
    assert m.itl_ms and m.ttft_ms       # tokens were actually produced


def test_permissive_policy_over_split_topology_uses_all_pools():
    """Pool wake-ups follow policy eligibility, not topology capability:
    SharedBaselinePolicy over a prefill/decode split must keep every
    pool busy (no silently idle devices)."""
    m = Engine(Topology.serving(8, 2), SharedBaselinePolicy(), PM).run(
        _workload(), 30_000)
    for pool in ("prefill", "decode"):
        assert sum(m.pool_busy.get(pool, {}).values()) > 0, m.pool_busy
    assert m.handoffs == 0              # light work decodes where placed


def test_all_cores_avx_config_still_schedules():
    """Pre-API behaviour preserved: n_avx_cores == n_cores collapses to
    one all-capability pool instead of raising."""
    s = Scheduler(SchedConfig(n_cores=2, n_avx_cores=2,
                              specialization=True))
    a = Task(iter(()), ttype=TaskType.AVX)
    b = Task(iter(()), ttype=TaskType.SCALAR)
    s.enqueue(a, 0.0)
    s.enqueue(b, 1.0)
    assert s.pick_next(0, 0.0) is a
    assert s.pick_next(1, 0.0) is b


def test_adaptive_resizing_converges_and_does_not_flap():
    """Start with a deliberately oversized prefill pool: the policy must
    shrink it toward the observed heavy share, then hold steady — no
    rapid back-and-forth."""
    pol = AdaptivePolicy()
    eng = Engine(Topology.serving(16, 8), pol, PM,
                 ServeConfig(resize_interval_ms=2000.0))
    m = eng.run(_workload(duration=120_000), 120_000)
    assert m.resize_events, "oversized pool was never resized"
    ts = [t for t, _ in m.resize_events]
    sizes = [d["prefill"] for _, d in m.resize_events]
    assert sizes[0] < 8                       # first move shrinks
    assert sizes[-1] <= 4                     # settles well below start
    assert len(sizes) <= 8                    # bounded churn
    # no flap: consecutive resizes never closer than two windows
    assert all(b - a >= 4000.0 for a, b in zip(ts, ts[1:]))
    # devices are conserved through every resize
    for _, d in m.resize_events:
        assert sum(d.values()) == 16


def test_engine_runs_are_independent():
    """run() always starts from the constructor topology: resizes from a
    previous run must not leak into the next."""
    eng = Engine(Topology.serving(16, 8), AdaptivePolicy(), PM,
                 ServeConfig(resize_interval_ms=2000.0))
    first = eng.run(_workload(duration=60_000), 60_000)
    assert first.resize_events          # the oversized pool was resized
    assert eng.topo.pool("prefill").n_units != 8
    second = eng.run(_workload(duration=60_000), 60_000)
    third = eng.run(_workload(duration=60_000), 60_000)
    # EMA state persists across runs (online learning), but by the second
    # run it has converged: identical workloads give identical results
    assert second.summary() == third.summary()


def test_adaptive_static_topology_is_specialized():
    """Between resizes the adaptive policy schedules exactly like the
    specialized one."""
    topo = Topology.serving(16, 4)
    ad, sp = AdaptivePolicy(), SpecializedPolicy()
    for kind in WorkKind:
        assert ad.placement(topo, kind) == sp.placement(topo, kind)
        for pool in topo:
            assert ad.eligible(topo, pool, kind) == \
                sp.eligible(topo, pool, kind)
    m_ad = Engine(topo, ad, PM, ServeConfig(resize_interval_ms=1e12)).run(
        copy.deepcopy(_workload()), 30_000)
    m_sp = Engine(topo, sp, PM).run(copy.deepcopy(_workload()), 30_000)
    assert m_ad.summary() == m_sp.summary()


# ---------------------------------------------- muqss conformance suite


def _drain(sched, core):
    out = []
    while True:
        t = sched.pick_next(core, 0.0)
        if t is None:
            return out
        out.append(t)
        sched.on_done(t, core)


@pytest.mark.parametrize("policy", [SpecializedPolicy(), AdaptivePolicy()])
def test_muqss_scalar_core_never_picks_avx_under_policy(policy):
    topo = Topology.cores(4, 1)
    s = Scheduler(SchedConfig(n_cores=4, n_avx_cores=1), topology=topo,
                  policy=policy)
    for tt in (TaskType.AVX, TaskType.SCALAR, TaskType.UNTYPED):
        s.enqueue(Task(iter(()), ttype=tt), 0.0)
    picked = _drain(s, 0)                      # core 0 is scalar
    assert all(t.ttype != TaskType.AVX for t in picked)
    assert len(picked) == 2


@pytest.mark.parametrize("policy",
                         [SharedBaselinePolicy(), CohortPolicy(4)])
def test_muqss_shared_policies_run_anything_anywhere(policy):
    topo = Topology.shared(2)
    s = Scheduler(SchedConfig(n_cores=2, specialization=False),
                  topology=topo, policy=policy)
    a = Task(iter(()), ttype=TaskType.AVX)
    b = Task(iter(()), ttype=TaskType.SCALAR)
    s.enqueue(a, 0.0)
    s.enqueue(b, 1.0)
    assert s.pick_next(0, 0.0) is a            # any core, EDF order


def test_muqss_and_engine_share_one_policy_object():
    """The same Policy instance drives both mechanisms."""
    pol = SpecializedPolicy()
    s = Scheduler(SchedConfig(n_cores=4, n_avx_cores=1),
                  topology=Topology.cores(4, 1), policy=pol)
    t = Task(iter(()), ttype=TaskType.AVX)
    core = s.enqueue(t, 0.0)
    assert s.is_avx_core(core)
    m = Engine(Topology.serving(8, 2), pol, PM).run(_workload(), 20_000)
    assert m.pool_busy["decode"]["heavy"] == 0.0

