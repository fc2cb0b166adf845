"""Decode updates its KV caches in place: each of the executor's compiled
round programs aliases every cache it is given to the cache it returns
and copies neither a cache nor a layer's slice of one, and the engine
served through that donating executor, one program a round, emits the
tokens of the plain, un-jitted decode of each request alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import Rounds, assert_updates_cache_in_place, reported_times
from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.launch.serve import RealModelExecutor, round_sizes
from repro.models.api import build_model
from repro.sched import SpecializedPolicy, Topology
from repro.sched.engine import Engine, Request, ServeConfig

PROMPT, BATCH = 16, 8
# staggered arrivals (ms) and lengths: with a prefill reported as 1 ms and
# a round as 3 ms, a request joins the decode pool each round, so the
# rounds grow from one sequence to four, then shrink through three (run
# as four) and two to one
ARRIVALS, MAX_NEW = [0.0, 0.5, 1.0, 1.5], [5, 6, 7, 8]
# qwen1.5: grouped-query attention; the DeepSeek-V3 share: latent
# attention, a leading dense layer, and held experts
ARCHS = ["qwen1.5-0.5b", "deepseek-v3-671b-ep32"]


def _executor(arch: str):
    # float32: at bfloat16 the CPU compiler adds conversion copies
    cfg = get_arch(arch).reduced()
    model = build_model(cfg, no_dist())
    params = model.init(jax.random.key(0))
    ex = RealModelExecutor(model, params, cfg.vocab, PROMPT,
                           PROMPT + max(MAX_NEW), jax.devices()[0], seed=7,
                           batch=BATCH)
    ex.compile()
    return model, params, ex


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    return _executor(request.param)


# the DeepSeek-V3 share's round programs are checked as the chip compiles
# them, in test_tpu_compile.py: the CPU compiler copies its small latent
# cache whatever the round's size
@pytest.mark.parametrize("served", ARCHS[:1], indirect=True)
@pytest.mark.parametrize("size", round_sizes(BATCH))
def test_compiled_decode_aliases_and_copies_no_cache(served, size):
    model, params, ex = served
    cache = jax.eval_shape(
        lambda: model.init_cache(params, None, 1, PROMPT + max(MAX_NEW)))
    assert_updates_cache_in_place(ex.decode_j[size], (cache,) * size)


def _plain_greedy(model, params, prompt, n):
    """``n`` greedy tokens from the un-jitted prefill and decode step."""
    toks = jnp.asarray(prompt)
    cache = model.init_cache(params, {"tokens": toks}, 1, PROMPT + n)
    logits, cache = model.prefill(params, {"tokens": toks}, cache)
    out = [int(jnp.argmax(logits[0]))]
    for i in range(n - 1):
        tok = jnp.full((1, 1), out[-1], jnp.int32)
        logits, cache = model.decode_step(
            params, cache, tok, jnp.full((1,), PROMPT + i, jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_engine_through_donating_executor_emits_plain_tokens(served):
    model, params, ex = served
    ex.state.clear()
    ex.tokens.clear()
    reqs = [Request(rid=i, arrive_ms=t, prompt_len=PROMPT, max_new=n)
            for i, (t, n) in enumerate(zip(ARRIVALS, MAX_NEW))]
    eng = Engine(Topology.serving(n_devices=2, prefill_devices=1),
                 SpecializedPolicy(),
                 cfg=ServeConfig(prefill_chunk=PROMPT, decode_batch_max=BATCH),
                 executor=ex)
    rounds = Rounds()
    with reported_times(ex):
        assert eng.run(reqs, oracle=rounds).completed == len(reqs)
    assert rounds.sizes == [1, 2, 3, 4, 3, 3, 2, 2, 1, 1]
    assert ex.state == {}            # every finished request's cache dropped
    got = ex.emitted()
    for rid, n in enumerate(MAX_NEW):
        want = _plain_greedy(model, params, ex.prompt(rid), n)
        np.testing.assert_array_equal(got[rid], want)


def test_executor_refuses_a_round_beyond_its_largest(served):
    _, _, ex = served
    ex.state.clear()
    n = round_sizes(BATCH)[-1] + 1
    reqs = [Request(rid=i, arrive_ms=0.0, prompt_len=PROMPT, max_new=2)
            for i in range(n)]
    with pytest.raises(ValueError, match="exceeds the executor's largest"):
        ex.decode(reqs, "decode", 1)
    assert ex.state == {}


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "rwkv6-3b",
                                  "whisper-large-v3"])
def test_executor_refuses_models_without_per_request_caches(arch):
    # the hybrid, recurrent and encoder-decoder steps decode one cache of a
    # batch, not a round over one cache per request
    model = build_model(get_arch(arch).reduced(), no_dist())
    with pytest.raises(ValueError, match="per-request caches"):
        RealModelExecutor(model, None, 256, PROMPT, PROMPT + 1,
                          jax.devices()[0])
