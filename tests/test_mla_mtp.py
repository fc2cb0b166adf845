"""DeepSeek-specific features: MLA absorbed-vs-naive decode equivalence
and the optional multi-token-prediction head."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import attention as attn
from repro.models import transformer


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("deepseek-v3-671b").reduced()
    p = attn.mla_init(jax.random.key(0), cfg, jnp.float32)
    return cfg, p


def test_mla_absorbed_decode_matches_naive(setup):
    cfg, p = setup
    B, S = 2, 12
    x = jax.random.normal(jax.random.key(1), (B, 1, cfg.d_model)) * 0.5
    cache_a = attn.mla_init_cache(cfg, B, 32, jnp.float32)
    cache_b = jax.tree_util.tree_map(lambda a: a.copy(), cache_a)
    # warm both caches identically
    warm = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model)) * 0.5
    _, cache_a = attn.mla_prefill(p, warm, cfg, cache_a,
                                  jnp.arange(S)[None].repeat(B, 0))
    _, cache_b = attn.mla_prefill(p, warm, cfg, cache_b,
                                  jnp.arange(S)[None].repeat(B, 0))
    lengths = jnp.full((B,), S, jnp.int32)
    y_abs, _ = attn.mla_decode_token(p, x, cfg, cache_a, lengths)
    y_naive, _ = attn.mla_decode_naive(p, x, cfg, cache_b, lengths)
    np.testing.assert_allclose(np.asarray(y_abs), np.asarray(y_naive),
                               rtol=2e-4, atol=2e-4)


def test_mla_cache_is_compressed(setup):
    """The MLA cache stores the latent (kv_lora + rope), not full KV —
    the property that makes migration/handoff cheap."""
    cfg, _ = setup
    cache = attn.mla_init_cache(cfg, 4, 64, jnp.float32)
    m = cfg.mla
    latent_elems = 4 * 64 * (m.kv_lora_rank + m.rope_head_dim)
    full_kv_elems = 4 * 64 * cfg.n_heads * (m.nope_head_dim + m.v_head_dim)
    total = sum(x.size for x in jax.tree_util.tree_leaves(cache))
    assert total == latent_elems
    assert total < full_kv_elems / 4


def test_mtp_head_trains():
    cfg = get_arch("deepseek-v3-671b").reduced()
    params = transformer.lm_init(jax.random.key(0), cfg)
    mtp = transformer.mtp_init(jax.random.key(1), cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab)
    t2 = jnp.roll(toks, -2, axis=1)
    loss = transformer.mtp_loss(params, mtp, toks, t2, cfg)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0
    g = jax.grad(lambda m: transformer.mtp_loss(params, m, toks, t2, cfg))(mtp)
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree_util.tree_leaves(g))
