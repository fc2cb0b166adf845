"""Flash-decode — one-token attention against a long KV cache.

Grid: (B*H, n_kv_blocks); the kv dimension is minor-most so the partial
(m, l, acc) state persists in VMEM scratch across a head's kv blocks.
Per-sequence valid lengths mask the tail block. The KV cache never
duplicates GQA heads (BlockSpec index map folds q head -> kv head).

This kernel is the serving hot path the device-pool scheduler tags as
"light"/memory-bound (decode), in contrast to flash_attention (prefill,
MXU-bound) — the two work kinds ``repro.sched`` places on separate pools.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
               *, block_k: int, scale: float, n_k: int, heads: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    b = bh // heads

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [1, D]
    k = k_ref[0].astype(jnp.float32)                    # [BK, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    s = jnp.where(pos < len_ref[b], s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k, v, lengths, *, block_k: int = 512,
                 interpret: bool = False):
    """q [B,H,D], k/v [B,KVH,S,D], lengths [B] -> [B,H,D]."""
    B, H, D = q.shape
    KVH, S = k.shape[1], k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    assert S % block_k == 0
    n_k = S // block_k
    qf = q.reshape(B * H, 1, D)
    kf = k.reshape(B * KVH, S, D)
    vf = v.reshape(B * KVH, S, D)
    kernel = functools.partial(_fd_kernel, block_k=block_k, scale=scale,
                               n_k=n_k, heads=H)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),      # lengths [B]
            pl.BlockSpec((1, 1, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, g=G: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, g=G: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qf, kf, vf)
    return out.reshape(B, H, D)
