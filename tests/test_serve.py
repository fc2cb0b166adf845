"""The serve entry point on the CPU at the reduced width: shard
placement, recorded tokens, the compile cache's location and the chip
smoke script's refusal to run without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from helpers import REPO, run_with_devices
from repro.launch import compile_cache, serve

SMALL = ["--reduced", "--requests", "4", "--prompt", "16", "--max-new", "4",
         "--batch", "2"]


def test_shard_devices_refuses_more_shards_than_devices():
    n = len(jax.devices())
    assert serve.shard_devices(n) == jax.devices()
    for bad in (0, n + 1):
        with pytest.raises(ValueError):
            serve.shard_devices(bad)


def test_engine_serves_every_request_with_finite_logits():
    run = serve.main(SMALL)
    ex = run.executors["engine"]
    assert run.metrics.completed == 4
    assert ex.all_finite()
    tokens = ex.emitted()
    assert sorted(tokens) == [0, 1, 2, 3]
    assert all(len(t) == 4 for t in tokens.values())
    assert ex.state == {}            # every cache dropped at completion
    assert run.compile_s > 0


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
