"""jit'd public wrappers for the Pallas kernels."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.chacha20 import keystream as chacha20_keystream
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention


def chacha20_encrypt(data_u32: jnp.ndarray, key: jnp.ndarray,
                     nonce: jnp.ndarray, counter0: int = 1,
                     interpret: bool = False) -> jnp.ndarray:
    """XOR data (flattened to u32 words, multiple of 16 per block) with the
    keystream. data_u32: [n_blocks, 16] u32."""
    n_blocks = data_u32.shape[0]
    tile = min(256, n_blocks)
    while n_blocks % tile:
        tile -= 1
    ks = chacha20_keystream(key, nonce, counter0, n_blocks=n_blocks,
                            tile=tile, interpret=interpret)
    return data_u32 ^ ks


__all__ = ["chacha20_keystream", "chacha20_encrypt", "flash_attention",
           "flash_decode"]
