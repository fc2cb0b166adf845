"""Flash attention (prefill/training fwd) — Pallas TPU kernel.

Blockwise online-softmax attention with GQA head folding and causal
block skipping. TPU grids execute sequentially along the minor-most
dimension, so the (m, l, acc) running state lives in VMEM scratch and
persists across the kv-block iterations of one q block; the causal upper
triangle is skipped with ``pl.when`` (on real hardware the skipped block
issues no MXU work — this is the half-FLOPs advantage over the XLA
reference path in ``repro.models.layers``).

Layout: q [BH, S, D] (B*H fused), k/v [BKV, S, D]; GQA maps q head bh to
kv head bh // group via the BlockSpec index map — no repeated kv in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, block_q: int, block_k: int, causal: bool,
               n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # [BQ, D]
        k = k_ref[0].astype(jnp.float32)                   # [BK, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks entirely above the diagonal (the real-TPU FLOPs win)
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q [B,H,S,D], k/v [B,KVH,S,D] -> [B,H,S,D]."""
    B, H, S, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    n_q, n_k = S // block_q, S // block_k
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * KVH, S, D)
    vf = v.reshape(B * KVH, S, D)

    kernel = functools.partial(_fa_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki, g=G: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki, g=G: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
