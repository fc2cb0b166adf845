"""Analyzer tests: cost-model properties, control-flow regressions (the
old ``_eqn_cost`` while/cond bugs), region segmentation invariants, the
static-vs-HLO differential pins, the calibration artifact, and the
intermittency lint."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.costs import CostConfig, jaxpr_cost
from repro.analysis.differential import differential
from repro.analysis.lint import lint_timeline, untagged_findings
from repro.analysis.regions import (Region, RegionTimeline, segment,
                                    segment_jaxpr, tag_heavy)

W = jnp.zeros((32, 32))


def _cost(fn, *args, cfg=CostConfig()):
    return jaxpr_cost(jax.make_jaxpr(fn)(*args).jaxpr, cfg)


# --------------------------------------------------- cost-model properties


def test_cost_additivity_over_composition():
    x = jnp.zeros((8, 32))

    def one(x):
        return x @ W

    def four(x):
        for _ in range(4):
            x = x @ W
        return x

    c1, c4 = _cost(one, x), _cost(four, x)
    assert c4.mxu_flops == pytest.approx(4 * c1.mxu_flops)
    assert c4.flops == pytest.approx(4 * c1.flops)
    assert c4.bytes == pytest.approx(4 * c1.bytes)


def test_scan_multiplies_through_nested_pjit():
    x = jnp.zeros((8, 32))
    body = jax.jit(lambda c, _: (c @ W, None))    # jit inside the scan

    def once(x):
        return x @ W

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    assert _cost(scanned, x).mxu_flops == pytest.approx(
        8 * _cost(once, x).mxu_flops)


def test_dtype_aware_bytes():
    def f(x):
        return x * 2.0 + 1.0

    b32 = _cost(f, jnp.zeros((64, 64), jnp.float32)).bytes
    b16 = _cost(f, jnp.zeros((64, 64), jnp.bfloat16)).bytes
    assert b32 == pytest.approx(2 * b16)


# ------------------------------------------- control-flow regressions


def test_while_counts_cond_and_assumed_trips():
    """The old pass dropped cond_jaxpr and ran the body exactly once."""
    x = jnp.zeros((8, 32))

    def body_only(x):
        return x @ W

    def looped(x):
        out, _ = jax.lax.while_loop(
            lambda c: c[1] < 5, lambda c: (c[0] @ W, c[1] + 1), (x, 0))
        return out

    one = _cost(body_only, x)
    for trips in (3, 8):
        c = _cost(looped, x, cfg=CostConfig(assumed_while_trips=trips))
        assert c.mxu_flops == pytest.approx(trips * one.mxu_flops)
        # cond (one `lt` flop) runs trips+1 times: body flops plus extra
        assert c.flops >= trips * one.flops + (trips + 1)


def test_cond_counts_branch_mxu_flops_as_max():
    """The old pass fell through to the pointwise path: branch MXU flops
    counted as ZERO."""
    x = jnp.zeros((8, 32))

    def branchy(x, pred):
        return jax.lax.cond(pred, lambda v: v @ W, lambda v: v, x)

    c = _cost(branchy, x, jnp.asarray(True))
    assert c.mxu_flops == pytest.approx(_cost(lambda v: v @ W, x).mxu_flops)


def test_cond_asymmetric_branches_flagged():
    x = jnp.zeros((8, 32))

    def branchy(x, pred):
        return jax.lax.cond(pred, lambda v: v @ W, lambda v: v, x)

    warnings = []
    jaxpr_cost(jax.make_jaxpr(branchy)(x, jnp.asarray(True)).jaxpr,
               CostConfig(), warnings)
    assert any("asymmetric cond branches" in w for w in warnings)


# ------------------------------------------------- region segmentation


def test_region_totals_equal_jaxpr_cost():
    """Segmentation is a partition: region sums reproduce the flat cost
    walk exactly, including through scan and while."""
    x = jnp.zeros((8, 32))

    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ W), None), x,
                            None, length=4)
        out, _ = jax.lax.while_loop(
            lambda c: c[1] < 5, lambda c: (c[0] @ W, c[1] + 1), (y, 0))
        return jnp.sum(out)

    closed = jax.make_jaxpr(f)(x)
    tl = segment_jaxpr(closed, name="f", fold_frac=0.0)
    c = jaxpr_cost(closed.jaxpr, CostConfig())
    assert tl.mxu_flops == pytest.approx(c.mxu_flops)
    assert tl.flops == pytest.approx(c.flops)
    assert tl.bytes == pytest.approx(c.bytes)


def test_fold_absorbs_sub_permille_regions():
    x = jnp.zeros((256, 256))

    def f(x):
        y = x @ W[:256, :256] if False else x @ jnp.zeros((256, 256))
        y = y[0, 0] + 1.0          # tiny scalar bookkeeping
        return y * jnp.sum(x)

    raw = segment(f, x, fold_frac=0.0)
    folded = segment(f, x)
    assert len(folded.regions) <= len(raw.regions)
    assert folded.flops == pytest.approx(raw.flops)


def test_tag_heavy_duty_criterion():
    """Tagging needs BOTH a heavy time share and a non-trivial share of
    the cohort's heavy time — a decode-analogue with tiny absolute heavy
    time stays untagged even when its own share is high."""
    big = RegionTimeline("prefill", [Region(0, 0, 2, 1e9, 1e9, 1e6,
                                            est_us=1000.0)], [])
    tiny = RegionTimeline("decode", [Region(0, 0, 2, 1e3, 1e3, 1e3,
                                            est_us=0.5)], [])
    cold = RegionTimeline("embed", [Region(0, 0, 0, 0.0, 1e3, 1e6,
                                           est_us=500.0)], [])
    assert tag_heavy([big, tiny, cold]) == ["prefill"]


# ------------------------------------------------ differential pins


@pytest.mark.slow
def test_differential_flash_attention_agrees():
    q = jnp.zeros((1, 4, 256, 64), jnp.float32)
    from repro.kernels.ops import flash_attention
    d = differential(lambda a, b, c: flash_attention(a, b, c, interpret=True),
                     q, q, q, name="flash_attention")
    assert d is not None and d.agrees, d.describe()


@pytest.mark.slow
def test_differential_model_prefill_agrees():
    from repro.analysis.calibrate import _model_differential
    d = _model_differential("qwen1.5-0.5b", tol=0.25)
    assert d is not None and d["agrees"], d


def test_chacha20_divergence_is_documented():
    from repro.analysis.calibrate import KNOWN_DIVERGENT
    from repro.analysis import derived
    assert "chacha20" in KNOWN_DIVERGENT
    rec = derived.load()["kernels"]["chacha20"]["differential"]
    assert rec["agrees"] is False      # pinned: interpret-mode HLO
    assert rec["static_mxu_flops"] == 0.0


# ---------------------------------------------- calibration artifact


def test_derived_artifact_covers_zoo():
    from repro.analysis import derived
    from repro.configs import arch_ids
    w = derived.workloads()
    assert sorted(w) == sorted(arch_ids())
    for arch, entry in w.items():
        f0, f1, f2 = entry["freq"]["levels_ghz"]
        assert f0 > f1 > f2 > 0
        assert entry["tags"], arch
        sw = entry["scenario"]["sim_work"]
        assert 0 < sw["prefill_cycles_per_tok"] <= 2 * 205.0
        assert 0 < sw["decode_cycles_per_tok"] <= 2 * 6000.0


def test_zoo_scenarios_registered():
    from repro.analysis import derived
    from repro.sched.workload import SCENARIOS, scenario_spec
    assert len(SCENARIOS) >= 15
    for arch in derived.workload_ids():
        name = f"zoo/{arch}"
        assert name in SCENARIOS
        spec = scenario_spec(name)
        assert spec.sim_work == derived.scenario_params(arch)["sim_work"]


def test_trace_tasks_honors_sim_work():
    from repro.core.workloads import (TRACE_DECODE_CYCLES_PER_TOK,
                                      TRACE_PREFILL_CYCLES_PER_TOK,
                                      _trace_request, trace_tasks)
    from repro.sched.workload import scenario_trace
    tr = scenario_trace("zoo/grok-1-314b", duration_ms=20_000, seed=0)
    sw = tr.meta["sim_work"]
    assert sw["decode_cycles_per_tok"] != TRACE_DECODE_CYCLES_PER_TOK
    assert len(trace_tasks(tr)) == len(tr.requests)
    items = list(_trace_request(100, 2, "avx512",
                                sw["prefill_cycles_per_tok"],
                                sw["decode_cycles_per_tok"]))
    segs = [s for s in items if hasattr(s, "cycles")]
    assert segs[0].cycles == pytest.approx(
        100 * sw["prefill_cycles_per_tok"])
    assert segs[1].cycles == pytest.approx(sw["decode_cycles_per_tok"])
    # a plain scenario (no sim_work meta) keeps the hand-tuned defaults
    tr0 = scenario_trace("steady", duration_ms=5_000, seed=0)
    assert "sim_work" not in tr0.meta
    items0 = list(_trace_request(100, 1, "avx512",
                                 TRACE_PREFILL_CYCLES_PER_TOK,
                                 TRACE_DECODE_CYCLES_PER_TOK))
    assert [s for s in items0 if hasattr(s, "cycles")][0].cycles == \
        pytest.approx(100 * TRACE_PREFILL_CYCLES_PER_TOK)


# ----------------------------------------------------------- lint


def _tl(name, levels_trips_us):
    regions = [Region(i, i, lvl, 0.0, 1.0, 1.0, est_us=us * trips,
                      trips=trips)
               for i, (lvl, trips, us) in enumerate(levels_trips_us)]
    return RegionTimeline(name, regions, [])


def test_lint_flags_short_heavy_sandwich():
    tl = _tl("f", [(1, 1, 5000.0), (2, 16, 100.0), (1, 1, 5000.0)])
    found = lint_timeline(tl, "wl")
    assert len(found) == 1
    f = found[0]
    assert f.kind == "license-thrash"
    assert f.severity == pytest.approx(16 * (2000.0 - 100.0))


def test_lint_ignores_long_or_unsandwiched_regions():
    # long heavy region: holds the license legitimately
    assert not lint_timeline(
        _tl("f", [(1, 1, 5000.0), (2, 1, 3000.0), (1, 1, 5000.0)]), "wl")
    # ascending levels: no sandwich
    assert not lint_timeline(
        _tl("f", [(0, 1, 100.0), (1, 1, 100.0), (2, 1, 100.0)]), "wl")


def test_lint_untagged_heavy_entrypoint():
    found = untagged_findings("zoo/x", ["prefill", "decode_step"],
                              ["prefill"], {"decode_step": 42.0})
    assert len(found) == 1
    assert found[0].kind == "untagged-heavy-entrypoint"
    assert found[0].entrypoint == "decode_step"
    assert not untagged_findings("zoo/x", ["prefill"],
                                 ["prefill", "decode_step"], {})


def test_lint_baseline_committed_and_clean_of_untagged():
    import json
    from repro.analysis.lint import BASELINE_PATH
    base = json.loads(BASELINE_PATH.read_text())
    assert base["n_untagged"] == 0
    assert base["n_findings"] == len(base["findings"])
    # ranked: severities non-increasing
    sevs = [f["severity"] for f in base["findings"]]
    assert sevs == sorted(sevs, reverse=True)
