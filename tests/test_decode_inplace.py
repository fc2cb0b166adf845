"""Decode updates its KV cache in place: the executor's compiled decode
aliases the cache it is given to the cache it returns and copies neither
the cache nor a layer's slice of it, and the engine served through that
donating executor emits the tokens of the plain, un-jitted decode."""
import jax
import jax.numpy as jnp
import numpy as np

from helpers import assert_updates_cache_in_place
from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.launch.serve import RealModelExecutor
from repro.models.api import build_model
from repro.sched import SpecializedPolicy, Topology
from repro.sched.engine import Engine, Request, ServeConfig

PROMPT = 16


def _executor(max_new: int):
    # float32: at bfloat16 the CPU compiler adds conversion copies
    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = build_model(cfg, no_dist())
    params = model.init(jax.random.key(0))
    ex = RealModelExecutor(model, params, cfg.vocab, PROMPT,
                           PROMPT + max_new, jax.devices()[0], seed=7)
    ex.compile()
    return model, params, ex


def test_compiled_decode_aliases_and_copies_no_cache():
    model, params, ex = _executor(48)
    cache = jax.eval_shape(
        lambda: model.init_cache(params, None, 1, PROMPT + 48))
    assert_updates_cache_in_place(ex.decode_j, cache)


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


def test_engine_through_donating_executor_emits_plain_tokens():
    max_new = [3, 7, 5]
    model, params, ex = _executor(max(max_new))
    reqs = [Request(rid=i, arrive_ms=float(i), prompt_len=PROMPT, max_new=n)
            for i, n in enumerate(max_new)]
    eng = Engine(Topology.serving(n_devices=2, prefill_devices=1),
                 SpecializedPolicy(),
                 cfg=ServeConfig(prefill_chunk=PROMPT, decode_batch_max=8),
                 executor=ex)
    assert eng.run(reqs).completed == len(reqs)
    assert ex.state == {}            # every finished request's cache dropped
    got = ex.emitted()
    for rid, n in enumerate(max_new):
        want = _plain_greedy(model, params, ex.prompt(rid), n)
        np.testing.assert_array_equal(got[rid], want)
