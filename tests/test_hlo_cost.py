"""Unit tests for the while-aware HLO cost model (the roofline's
foundation): trip-count multiplication, fusion flops/bytes attribution,
in-place update accounting, collective wire formulas."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo_cost import analyze, parse_module, xla_cost_analysis
from repro.roofline import analysis as ra


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_trip_count_multiplies():
    w = jnp.zeros((128, 128), jnp.float32)

    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None,
                            length=12)
        return y.sum()

    t = analyze(_compile(f, jnp.zeros((128, 128))).as_text())
    expect = 2 * 128 ** 3 * 12
    assert abs(t.flops - expect) / expect < 0.02


def test_nested_scan_multiplies():
    w = jnp.zeros((64, 64), jnp.float32)

    def f(x):
        def outer(c, _):
            y, _ = jax.lax.scan(lambda a, _: (a @ w, None), c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y.sum()

    t = analyze(_compile(f, jnp.zeros((64, 64))).as_text())
    expect = 2 * 64 ** 3 * 15
    assert abs(t.flops - expect) / expect < 0.05


def test_xla_cost_analysis_undercounts_scans():
    """The reason hlo_cost exists: XLA's cost analysis counts a scan's
    body once, whatever its trip count."""
    w = jnp.zeros((128, 128), jnp.float32)

    def mk(n):
        def f(x):
            y, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=n)
            return y.sum()
        return _compile(f, jnp.zeros((128, 128)))

    xla1 = xla_cost_analysis(mk(1))["flops"]
    xla16 = xla_cost_analysis(mk(16))["flops"]
    assert abs(xla1 - xla16) < 100   # XLA: scan body counted once
    ours16 = analyze(mk(16).as_text()).flops
    assert ours16 > 10 * xla16    # ours: multiplied by trip count


def test_inplace_update_bytes_small():
    """Scatter into a big buffer must cost ~the slice, not the buffer."""
    buf = jnp.zeros((4096, 1024), jnp.float32)   # 16 MB

    def f(buf, upd):
        return jax.lax.dynamic_update_slice(buf, upd, (0, 0))

    t = analyze(_compile(f, buf, jnp.zeros((1, 1024))).as_text())
    assert t.bytes < 2e6, t.bytes   # 16 MB buffer NOT counted as traffic


def test_dynamic_slice_bytes_small():
    buf = jnp.zeros((4096, 1024), jnp.float32)

    def f(buf):
        return jax.lax.dynamic_slice(buf, (0, 0), (2, 1024)) * 2.0

    t = analyze(_compile(f, buf).as_text())
    assert t.bytes < 1e6, t.bytes


def test_parse_module_finds_computations():
    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c * 2, None), x, None, length=4)
        return y

    comps = parse_module(_compile(f, jnp.zeros((32,))).as_text())
    assert any("main" in name for name in comps)
    assert len(comps) >= 2          # entry + loop body at least


def test_roofline_terms_and_bottleneck():
    r = ra.Roofline(arch="x", shape="train_4k", mesh="single", chips=256,
                    hlo_gflops=197_000.0,   # exactly 1 s of compute
                    hlo_gbytes=819.0,       # 1 s of HBM at the UB
                    floor_gbytes=81.9,      # 0.1 s floor
                    wire_gbytes=200.0,      # 2 s of ICI
                    model_gflops_total=197_000.0 * 256).finalize()
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_floor_s == pytest.approx(0.1)
    assert r.collective_s == pytest.approx(2.0)
    assert r.bottleneck == "collective"
    assert r.useful_flops_ratio == pytest.approx(1.0)
    assert r.mfu == pytest.approx(0.5)      # 1 s ideal / 2 s step


def test_model_flops_shapes():
    from repro.configs import get_arch, get_shape
    cfg = get_arch("qwen1.5-0.5b")
    tr = ra.model_flops(cfg, get_shape("train_4k"))
    pf = ra.model_flops(cfg, get_shape("prefill_32k"))
    dc = ra.model_flops(cfg, get_shape("decode_32k"))
    n = cfg.active_param_count()
    assert tr == pytest.approx(6 * n * 4096 * 256)
    assert pf == pytest.approx(2 * n * 32768 * 32)
    assert dc == pytest.approx(2 * n * 128)
