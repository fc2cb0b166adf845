"""ChaCha20 keystream kernel — the paper's AVX hot spot, TPU-adapted.

The x86 implementations vectorize the 20 ChaCha rounds across SIMD lanes
(4 blocks per YMM register with AVX2, 8 with AVX-512 — exactly the code
that drops the frequency license). The TPU adaptation runs the same
lane-parallel formulation across the VPU's 8x128 lanes: each kernel
invocation materializes a [TILE, 16] u32 state tile in VMEM (one row per
64-byte block, one column per state word) and applies the quarter-round
schedule column-wise, so every u32 op is a full-width VPU op. No MXU use
— this is deliberately a VPU kernel, matching the paper's workload class.

Grid: one program per TILE consecutive block counters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 256            # blocks (64 B each) per kernel invocation

_CONSTANTS = (0x61707865, 0x3320646e, 0x79622d32, 0x6b206574)

# quarter-round column schedule: (a, b, c, d) state indices
_QR = [(0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),
       (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14)]


def _rotl(x, n):
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


def _double_round(cols):
    for a, b, c, d in _QR:
        xa, xb, xc, xd = cols[a], cols[b], cols[c], cols[d]
        xa = xa + xb
        xd = _rotl(xd ^ xa, 16)
        xc = xc + xd
        xb = _rotl(xb ^ xc, 12)
        xa = xa + xb
        xd = _rotl(xd ^ xa, 8)
        xc = xc + xd
        xb = _rotl(xb ^ xc, 7)
        cols[a], cols[b], cols[c], cols[d] = xa, xb, xc, xd
    return cols


def _chacha20_kernel(key_ref, nonce_ref, ctr_ref, out_ref):
    """key [8]u32, nonce [3]u32, ctr [1]u32 (base), out [TILE, 16]u32."""
    tile = out_ref.shape[0]
    pid = pl.program_id(0)
    base = ctr_ref[0] + jnp.uint32(pid * tile)
    counters = base + jax.lax.broadcasted_iota(jnp.uint32, (tile,), 0)
    cols = []
    for i in range(4):
        cols.append(jnp.full((tile,), jnp.uint32(_CONSTANTS[i])))
    for i in range(8):
        cols.append(jnp.broadcast_to(key_ref[i], (tile,)))
    cols.append(counters)
    for i in range(3):
        cols.append(jnp.broadcast_to(nonce_ref[i], (tile,)))
    init = list(cols)
    for _ in range(10):
        cols = _double_round(cols)
    out = [c + i0 for c, i0 in zip(cols, init)]
    out_ref[...] = jnp.stack(out, axis=1)


def keystream(key: jnp.ndarray, nonce: jnp.ndarray, counter0: int,
              *, n_blocks: int, tile: int = TILE,
              interpret: bool = False) -> jnp.ndarray:
    """ChaCha20 keystream: [n_blocks, 16] u32 (64 bytes per row).

    key: [8] u32 (little-endian words), nonce: [3] u32, counter0: scalar
    (any value in [0, 2^32) — converted outside the jit boundary)."""
    ctr = jnp.asarray([int(counter0) & 0xFFFFFFFF], dtype=jnp.uint32)
    return _keystream(key, nonce, ctr, n_blocks=n_blocks, tile=tile,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_blocks", "tile", "interpret"))
def _keystream(key: jnp.ndarray, nonce: jnp.ndarray, ctr: jnp.ndarray,
               *, n_blocks: int, tile: int = TILE,
               interpret: bool = False) -> jnp.ndarray:
    assert n_blocks % tile == 0, (n_blocks, tile)
    grid = (n_blocks // tile,)
    return pl.pallas_call(
        _chacha20_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8,), lambda i: (0,)),
            pl.BlockSpec((3,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((tile, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, 16), jnp.uint32),
        interpret=interpret,
    )(key.astype(jnp.uint32), nonce.astype(jnp.uint32), ctr)
