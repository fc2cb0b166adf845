"""A decode round keeps its rows apart: each row of a round of two or of
four sequences, each over its own cache and at its own length, gets the
logits that a round of that row alone gives it. A change to how a round
lays out its projections that mixed rows would move them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.dist.context import no_dist
from repro.models.api import build_model

# each row's prompt, and the rows' lengths, no two alike: a row at length
# n attends over the first n positions of its prompt's cache
PROMPT, LENGTHS, MAX_SEQ = 16, (5, 9, 12, 16), 24
# bfloat16's resolution at 1: rows that a round mixed differ by about the
# logits' own size, some tenths here
TOL = {"rtol": 2**-8, "atol": 2**-8}


@pytest.fixture(scope="module",
                params=["qwen1.5-0.5b", "deepseek-v3-671b-ep32"])
def rows(request):
    """The reduced model at bfloat16, as the chip serves it, and a row
    at each of ``LENGTHS``: its cache after a prompt of its own, a token
    and its length."""
    cfg = dataclasses.replace(get_arch(request.param).reduced(),
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    model = build_model(cfg, no_dist())
    params = model.init(jax.random.key(0))

    @jax.jit
    def prefill(toks):
        cache = model.init_cache(params, {"tokens": toks}, 1, MAX_SEQ)
        return model.prefill(params, {"tokens": toks}, cache)[1]

    out = []
    for i, n in enumerate(LENGTHS):
        toks = jax.random.randint(jax.random.key(i + 1), (1, PROMPT), 0,
                                  cfg.vocab)
        out.append((prefill(toks), toks[:, n - 1:n],
                    jnp.full((1,), n, jnp.int32)))
    return model, params, out


@pytest.mark.parametrize("size", [2, 4])
def test_round_gives_each_row_its_logits_alone(rows, size):
    model, params, rs = rows
    step = jax.jit(model.decode_step)
    caches, toks, lengths = zip(*rs[:size])
    together, _ = step(params, caches, jnp.concatenate(toks),
                       jnp.concatenate(lengths))
    assert not np.allclose(together[0], together[1], **TOL)
    for i in range(size):
        alone, _ = step(params, caches[i:i + 1], toks[i], lengths[i])
        np.testing.assert_allclose(together[i], alone[0], **TOL)
