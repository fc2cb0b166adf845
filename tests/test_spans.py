"""The executor's profiler annotations on a real model at the reduced
width, the serve paths' output, and the names of the compiled steps that
the device-trace metrics look for."""
import os
import re
import subprocess
import sys

import jax
import pytest

from helpers import REPO, Rounds, reported_times
from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.launch import serve
from repro.launch.serve import RealModelExecutor
from repro.models.api import build_model
from repro.sched import SpecializedPolicy, Topology
from repro.sched.engine import Engine, Request, ServeConfig

PROMPT, MAX_NEW, BATCH = 8, 3, 2


@pytest.fixture(scope="module")
def executor():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = build_model(cfg, no_dist())
    params = jax.jit(model.init)(jax.random.key(0))
    ex = RealModelExecutor(model, params, cfg.vocab, PROMPT,
                           PROMPT + MAX_NEW, jax.devices()[0], batch=BATCH)
    ex.compile()
    return ex


def requests(n: int) -> list:
    return [Request(rid=i, arrive_ms=0.5 * i, prompt_len=PROMPT,
                    max_new=MAX_NEW) for i in range(n)]


def engine(executor) -> Engine:
    return Engine(Topology.serving(n_devices=2, prefill_devices=1),
                  SpecializedPolicy(),
                  cfg=ServeConfig(prefill_chunk=PROMPT,
                                  decode_batch_max=BATCH),
                  executor=executor)


@pytest.fixture
def annotations(monkeypatch):
    """Every profiler annotation entered and left while the test runs, as
    ("enter" | "exit", name)."""
    seen = []

    class Recorded:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
    return seen


def test_prefill_annotates_upload_dispatch_and_sync(executor, annotations):
    executor.state.clear()
    req = requests(1)[0]
    assert executor.prefill(req, PROMPT, "prefill", 1) > 0
    # one after the other, none nested in another
    assert annotations == [(k, n) for n in ("executor.upload",
                                            "executor.dispatch",
                                            "executor.sync")
                           for k in ("enter", "exit")]
    annotations.clear()
    # a later chunk of a prompt already run makes no call
    assert executor.prefill(req, PROMPT, "prefill", 1) == 0.0
    assert annotations == []
    executor.state.clear()


def test_each_decode_call_is_one_dispatch_and_one_sync(executor,
                                                       annotations):
    executor.state.clear()
    rounds = Rounds()
    with reported_times(executor):
        m = engine(executor).run(requests(3), oracle=rounds)
    assert m.completed == 3
    names = [n for k, n in annotations if k == "enter"]
    assert [n for k, n in annotations if k == "exit"] == names
    assert sum(rounds.sizes) == 3 * (MAX_NEW - 1)
    assert max(rounds.sizes) == BATCH
    calls = 3 + len(rounds.sizes)      # a prefill each, then one a round
    assert names.count("executor.upload") == 3
    assert names.count("executor.dispatch") == calls
    assert names.count("executor.sync") == calls
    # every dispatch is followed by the wait for its token
    for i, n in enumerate(names):
        if n == "executor.dispatch":
            assert names[i + 1] == "executor.sync"


def test_engine_imports_without_jax():
    code = ("import sys, repro.sched.engine; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_tokens_are_the_same_under_the_profiler(executor, tmp_path):
    executor.state.clear()
    executor.tokens.clear()
    engine(executor).run(requests(2))
    plain = executor.emitted()
    executor.tokens.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine(executor).run(requests(2))
    finally:
        jax.profiler.stop_trace()
    assert executor.emitted() == plain
    executor.tokens.clear()


@pytest.mark.parametrize("mode", [[], ["--mode", "cluster", "--shards", "1"]])
def test_serve_prints_no_simulated_license(mode, capsys):
    serve.main(["--reduced", "--requests", "2", "--prompt", "8",
                "--max-new", "2", "--batch", "2", *mode])
    out = capsys.readouterr().out
    assert "requests" in out
    # the CPU frequency license is simulated, not read from the device
    for word in ("frequency domains", "GHz", "residency=", "E="):
        assert word not in out


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_compiled_steps_carry_the_names_the_trace_reads(executor, step):
    # bench/trace.py finds the steps' device time as jit_prefill and
    # jit_decode programs: the decode of every round size
    programs = {"prefill": {1: executor.prefill_j},
                "decode": executor.decode_j}[step]
    if step == "decode":
        assert tuple(programs) == serve.round_sizes(BATCH)
    for compiled in programs.values():
        text = compiled.as_text()
        assert re.match(rf"HloModule jit_{step}\b", text), text[:200]


SCOPES = ("mla", "moe.route", "moe.experts", "moe.shared", "mlp.dense")


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_compiled_steps_carry_the_layer_scopes(step):
    """The op metadata of the compiled steps names the layer each op runs
    in (``jax.named_scope``), so that a trace reader can add up device
    time by scope: latent attention, the router, the held experts, the
    shared expert and the dense MLP of the leading layers."""
    cfg = get_arch("deepseek-v3-671b-ep32").reduced()
    model = build_model(cfg, no_dist())
    params = model.abstract_params()
    prefill, decode = serve.jit_steps(serve.serve_steps(model, PROMPT + 4))
    toks = jax.ShapeDtypeStruct((1, PROMPT), jax.numpy.int32)
    tok, _, cache, lengths = jax.eval_shape(prefill, params, toks)
    # decode as the executor runs a round of two, one cache each
    rows = [(x,) * 2 for x in (cache, tok, lengths)]
    fn, args = {"prefill": (prefill, (params, toks)),
                "decode": (decode, (params, *rows))}[step]
    names = re.findall(r'op_name="([^"]*)"', fn.lower(*args).compile()
                       .as_text())
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
