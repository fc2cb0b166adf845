"""§3.3 identification workflow: static analysis + perf counters +
flame graph + cross-check, and the adaptive policy (§4.3)."""
import jax
import jax.numpy as jnp

from repro.analysis import (analyze_jaxpr, rank_functions, report, segment,
                            tag_heavy)
from repro.core.adaptive import AdaptiveConfig, AdaptivePolicy
from repro.core.muqss import SchedConfig
from repro.core.perfcounters import CounterReport, cross_check
from repro.core.simulator import Simulator
from repro.core.workloads import WebConfig, webserver_tasks


def _heavy_light(d=64):
    w = jnp.zeros((d, d))

    def heavy(x):
        for _ in range(4):
            x = x @ w
        return x

    def light(x):
        return jnp.tanh(x) * 2 + 1
    return heavy, light, d


def test_static_analysis_ranks_matmul_heavy_first():
    heavy, light, d = _heavy_light()
    ranked = rank_functions([
        ("light", light, (jnp.zeros((8, d)),)),
        ("heavy", heavy, (jnp.zeros((8, d)),)),
    ])
    assert ranked[0].name == "heavy"
    assert ranked[0].heavy_ratio > 0.9
    assert ranked[1].heavy_ratio < 0.1
    assert "heavy" in report(ranked)


def test_region_report_and_tags_match_ranking():
    """The region-timeline pass agrees with the whole-function ranking:
    the matmul chain is an mxu-class timeline whose report names the
    regions, and tag_heavy selects it over the pointwise function."""
    heavy, light, d = _heavy_light()
    tl_heavy = segment(heavy, jnp.zeros((128, d)), name="heavy")
    # a (4,)-element pointwise op is scalar-class bookkeeping (below one
    # VPU lane row) — the decode-analogue the duty criterion must untag
    tl_light = segment(light, jnp.zeros((4,)), name="light")
    assert tl_heavy.mxu_flops > 0
    assert tl_heavy.heavy_share > 0.9
    assert any(r.klass == "heavy" and r.unit == "mxu"
               for r in tl_heavy.regions)
    rep = tl_heavy.report()
    assert "mxu" in rep and "dot_general" in rep
    assert "heavy" in tag_heavy([tl_heavy, tl_light])
    assert "light" not in tag_heavy([tl_heavy, tl_light])


def test_static_analysis_scan_multiplies():
    w = jnp.zeros((32, 32))

    def once(x):
        return x @ w

    def scanned(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=8)
        return y

    p1 = analyze_jaxpr(once, jnp.zeros((4, 32)))
    p8 = analyze_jaxpr(scanned, jnp.zeros((4, 32)))
    assert abs(p8.mxu_flops / p1.mxu_flops - 8.0) < 0.01


def test_throttle_flamegraph_localizes_better_than_cycles():
    """§3.3 faithfully reproduced: the THROTTLE flame graph (a) makes the
    crypto stand out far beyond its share of total cycles, and (b) still
    contains trailing-code false positives (the 0.5 ms window covers code
    after the trigger) — which is exactly why the paper cross-checks
    against static analysis."""
    scfg = SchedConfig(n_cores=12, n_avx_cores=0, specialization=False)
    sim = Simulator(scfg)
    for t in webserver_tasks(WebConfig(isa="avx512")):
        sim.add_task(t)
    sim.run(300_000)
    thr = {"/".join(k): v for k, v in sim.metrics.flame_throttle.items()}
    cyc = {"/".join(k): v for k, v in sim.metrics.flame_cycles.items()}
    crypto_thr = sum(v for k, v in thr.items() if "chacha20" in k)
    crypto_cyc = sum(v for k, v in cyc.items() if "chacha20" in k)
    share_thr = crypto_thr / max(sum(thr.values()), 1e-9)
    share_cyc = crypto_cyc / max(sum(cyc.values()), 1e-9)
    assert crypto_thr > 0
    assert share_thr > 2.0 * share_cyc          # localization
    brotli_thr = sum(v for k, v in thr.items() if "brotli" in k)
    assert brotli_thr > 0                        # the documented smearing


def test_lvl2_counter_smears_into_scalar_code():
    """LVL2 residency >> throttle-attributed crypto time: the 2 ms tail
    charges innocent scalar code (why the paper uses THROTTLE, §3.3)."""
    scfg = SchedConfig(n_cores=12, n_avx_cores=0, specialization=False)
    sim = Simulator(scfg)
    for t in webserver_tasks(WebConfig(isa="avx512")):
        sim.add_task(t)
    sim.run(300_000)
    c = sim.counters()
    crypto_cycles = sum(v for k, v in sim.metrics.flame_cycles.items()
                        if "chacha20" in "/".join(k))
    assert c["LVL2_TURBO_LICENSE"] > 3 * crypto_cycles


def test_cross_check_drops_false_positives():
    rep = CounterReport(
        counters={f"LVL{i}_TURBO_LICENSE": 0 for i in range(3)},
        flame_throttle={("nginx", "chacha20_avx512"): 100.0,
                        ("nginx", "brotli"): 40.0},
        flame_cycles={})

    class P:
        def __init__(self, name, ratio):
            self.name, self.heavy_ratio = name, ratio
    ranked = [P("chacha20_avx512", 0.9), P("brotli", 0.01)]
    out = cross_check(rep, ranked)
    assert "chacha20_avx512" in out
    assert "brotli" not in out


def test_adaptive_policy_enables_when_beneficial():
    pol = AdaptivePolicy(AdaptiveConfig(), n_cores=12)
    st = pol.update(scalar_share=0.95, heavy_share=0.05,
                    l2_residency=0.35, type_changes_per_s=55_000)
    assert st.enabled
    assert 1 <= st.n_avx_cores <= 3


def test_adaptive_policy_disables_at_extreme_change_rates():
    pol = AdaptivePolicy(AdaptiveConfig(), n_cores=12)
    st = pol.update(scalar_share=0.99, heavy_share=0.01,
                    l2_residency=0.02, type_changes_per_s=5_000_000)
    assert not st.enabled


def test_adaptive_pool_scales_with_heavy_share():
    pol = AdaptivePolicy(AdaptiveConfig(), n_cores=12)
    small = pol.pool_size(0.05)
    big = pol.pool_size(0.5)
    assert big > small
