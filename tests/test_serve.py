"""The serve entry point on the CPU at the reduced width: shard
placement, recorded tokens, replayed traces, fault plans, what it
imports, the compile cache's location and the chip smoke script's
refusal to run without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from helpers import REPO, run_with_devices
from repro.launch import compile_cache, serve
from repro.sched.workload import load_trace

SMALL = ["--reduced", "--requests", "4", "--prompt", "16", "--max-new", "4",
         "--batch", "2"]


def test_shard_devices_refuses_more_shards_than_devices():
    n = len(jax.devices())
    assert serve.shard_devices(n) == jax.devices()
    for bad in (0, n + 1):
        with pytest.raises(ValueError):
            serve.shard_devices(bad)


@pytest.mark.parametrize("mode", [[], ["--mode", "cluster", "--shards", "1"]],
                         ids=["engine", "cluster"])
def test_engine_serves_every_request_with_finite_logits(mode):
    run = serve.main(SMALL + mode)
    (ex,) = run.executors.values()
    assert run.metrics.summary()["completed"] == 4
    assert ex.all_finite()
    tokens = ex.emitted()
    assert sorted(tokens) == [0, 1, 2, 3]
    assert all(len(t) == 4 for t in tokens.values())
    assert ex.state == {}            # every cache dropped at completion
    assert run.compile_s > 0


def test_serve_imports_no_analyzer():
    # the serving path reads derived.json's license levels, never the
    # region analyzer or the roofline model behind it
    out = run_with_devices(
        "import sys\nimport repro.launch.serve\n"
        "print([m for m in ('repro.analysis.regions', "
        "'repro.roofline.analysis') if m in sys.modules])", n_devices=1)
    assert out.strip() == "[]"


def test_serve_replays_a_scenario_trace(monkeypatch):
    served = []

    class Recording(serve.Engine):
        def run(self, reqs, *args, **kwargs):
            served.extend((r.rid, r.arrive_ms) for r in reqs)
            return super().run(reqs, *args, **kwargs)

    monkeypatch.setattr(serve, "Engine", Recording)
    run = serve.main(SMALL + ["--workload", "bursty"])
    trace = load_trace("bursty", seed=0).requests[:4]
    assert served == [(r.rid, r.arrive_ms) for r in trace]
    assert len({t for _, t in served}) > 1   # not all due at once
    assert run.metrics.completed == 4
    tokens = run.executors["engine"].emitted()
    assert sorted(tokens) == sorted(r.rid for r in trace)
    assert all(len(t) == 4 for t in tokens.values())


def test_cluster_serve_runs_a_fault_plan(monkeypatch, capsys):
    horizons = []

    class Recording(serve.ClusterEngine):
        def run(self, reqs, *args, **kwargs):
            horizons.append((args, kwargs.get("fault_plan")))
            return super().run(reqs, *args, **kwargs)

    monkeypatch.setattr(serve, "ClusterEngine", Recording)
    run = serve.main(SMALL + ["--mode", "cluster", "--shards", "1",
                              "--fault-plan", "none"])
    ((args, plan),) = horizons
    assert plan.name == "none"
    assert len(args) == 1 and args[0] < float("inf")   # a finite horizon
    s = run.metrics.summary()
    assert s["completed"] == 4
    assert s["faults_injected"] == 0
    assert "[serve] faults: injected=0" in capsys.readouterr().out
    assert len(run.executors["shard0"].emitted()) == 4


def test_cluster_shards_hold_params_on_their_own_device():
    code = f"""
import jax
from repro.launch import serve
args = {SMALL!r} + ["--mode", "cluster"]
two = serve.main(args + ["--shards", "2"])
one = serve.main(args + ["--shards", "1"])
ids = []
for ex in two.executors.values():
    held = {{d.id for t in (ex.params, ex.tokens)
            for leaf in jax.tree.leaves(t) for d in leaf.devices()}}
    assert held == {{ex.device.id}}, held
    ids.append(ex.device.id)
assert sorted(ids) == [0, 1], ids
assert two.metrics.summary()["completed"] == 4
tokens = {{}}
for ex in two.executors.values():
    tokens.update(ex.emitted())
assert tokens == one.executors["shard0"].emitted()
print("OK")
"""
    assert "OK" in run_with_devices(code, n_devices=2)


def test_compile_cache_honours_env(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(REPO / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
