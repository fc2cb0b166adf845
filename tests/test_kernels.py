"""Per-kernel validation: RFC test vector, ref-oracle allclose, and
hypothesis shape/dtype sweeps. Every call passes ``interpret=True``: the
kernels default to compiled mode, which only a TPU can lower."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.chacha20 import keystream
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention

# ------------------------------------------------------------- chacha20

RFC_KEY = np.frombuffer(bytes(range(32)), dtype="<u4")
RFC_NONCE = np.frombuffer(bytes.fromhex("000000090000004a00000000"),
                          dtype="<u4")
RFC_BLOCK1 = bytes.fromhex(
    "10f1e7e4d13b5915500fdd1fa32071c4c7d1f4c733c068030422aa9ac3d46c4e"
    "d2826446079faa0914c2d705d98b02a2b5129cd1de164eb9cbd083e8a2503c4e")


def test_chacha20_rfc7539_vector():
    ks = keystream(jnp.asarray(RFC_KEY), jnp.asarray(RFC_NONCE), 1,
                   n_blocks=4, tile=4, interpret=True)
    got = np.asarray(ks[0]).astype("<u4").tobytes()
    assert got == RFC_BLOCK1


def test_chacha20_matches_ref_many_blocks():
    key = jnp.arange(8, dtype=jnp.uint32) * 0x01010101
    nonce = jnp.asarray([7, 11, 13], dtype=jnp.uint32)
    ks = keystream(key, nonce, 42, n_blocks=512, tile=128, interpret=True)
    want = ref.chacha20_keystream_ref(key, nonce, 42, 512)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 8))
def test_chacha20_property_counter_and_tiles(ctr, tiles):
    key = jnp.asarray(np.random.RandomState(ctr % 97).randint(
        0, 2**31, size=8), dtype=jnp.uint32)
    nonce = jnp.asarray([1, 2, 3], dtype=jnp.uint32)
    n = 16 * tiles
    ks = keystream(key, nonce, ctr, n_blocks=n, tile=16, interpret=True)
    want = ref.chacha20_keystream_ref(key, nonce, ctr, n)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(want))


# ------------------------------------------------------ flash attention


def _mk_qkv(key, B, H, KVH, S, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (B, KVH, S, D), dtype=jnp.float32)
    v = jax.random.normal(ks[2], (B, KVH, S, D), dtype=jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("B,H,KVH,S,D,dtype", [
    (1, 2, 2, 128, 32, jnp.float32),
    (2, 4, 2, 256, 64, jnp.float32),
    (1, 8, 2, 128, 64, jnp.bfloat16),
    (2, 2, 1, 512, 16, jnp.float32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_allclose(B, H, KVH, S, D, dtype, causal):
    q, k, v = _mk_qkv(jax.random.key(0), B, H, KVH, S, D, dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([64, 128, 192]), st.sampled_from([16, 32, 64]),
       st.sampled_from([1, 2, 4]), st.booleans())
def test_flash_attention_property(S, D, G, causal):
    KVH = 2
    q, k, v = _mk_qkv(jax.random.key(S * D * G), 1, KVH * G, KVH, S, D,
                      jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- flash decode


@pytest.mark.parametrize("B,H,KVH,S,D,dtype", [
    (2, 4, 2, 256, 64, jnp.float32),
    (1, 8, 4, 1024, 32, jnp.float32),
    (3, 2, 2, 512, 64, jnp.bfloat16),
])
def test_flash_decode_allclose(B, H, KVH, S, D, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KVH, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KVH, S, D)).astype(dtype)
    lengths = jnp.asarray([S // 2, S, 7][:B][:B] + [S] * max(0, B - 3))[:B]
    got = flash_decode(q, k, v, lengths, block_k=128, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.sampled_from([128, 256]),
       st.sampled_from([32, 64]), st.integers(1, 300))
def test_flash_decode_property_lengths(B, S, D, length):
    length = min(length, S)
    ks = jax.random.split(jax.random.key(B * S + D + length), 3)
    q = jax.random.normal(ks[0], (B, 4, D))
    k = jax.random.normal(ks[1], (B, 2, S, D))
    v = jax.random.normal(ks[2], (B, 2, S, D))
    lengths = jnp.full((B,), length, jnp.int32)
    got = flash_decode(q, k, v, lengths, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
