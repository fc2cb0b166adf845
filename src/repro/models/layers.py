"""Shared layers: norms, RoPE, dense/GLU MLPs, chunked flash attention.

Everything is a plain function over dict params; scanned stacks add a
leading layer axis. ``compute_dtype`` casting happens at matmul inputs;
norms/softmax/logits run in fp32.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------- dtypes

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def dt(name: str):
    return DTYPES[name]


# ----------------------------------------------------------------- norms

def init_norm(d: int, norm: str, dtype) -> dict:
    p = {"scale": jnp.ones((d,), dtype=dtype)}
    if norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype=dtype)
    return p


def apply_norm(p: dict, x: jnp.ndarray, norm: str, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------ RoPE

def yarn_range(head_dim: int, theta: float, yarn) -> tuple:
    """YaRN's correction range: the first and last rotary pair whose
    frequency is ramped between extrapolation and interpolation."""
    def dim(rotations):
        return head_dim * math.log(yarn.original_max_position
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(dim(yarn.beta_fast)), 0),
            min(math.ceil(dim(yarn.beta_slow)), head_dim - 1))


def yarn_mscale(yarn) -> float:
    """YaRN's attention temperature factor; the softmax scale is
    multiplied by its square."""
    return 0.1 * yarn.mscale_all_dim * math.log(yarn.factor) + 1.0


def rope_freqs(head_dim: int, theta: float, yarn=None) -> jnp.ndarray:
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return freqs
    if yarn.mscale != yarn.mscale_all_dim:
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos and "
                         "sin, which is not implemented")
    low, high = yarn_range(head_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None, interleaved: bool = False) -> jnp.ndarray:
    """x: [..., S, ..., D] with positions broadcastable to x's S dim.

    x layout: [B, S, H, D]; positions: [B, S] or [S]. Rotates the two
    halves of D, or with ``interleaved`` the pairs (0, 1), (2, 3), ...;
    ``yarn`` (a ``YarnConfig``) scales the frequencies.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, yarn)               # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(ang)[..., None, :]                  # [B, S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    if interleaved:
        xp = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
        x1, x2 = xp[..., 0], xp[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- MLP / GLU

def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False) -> dict:
    std = 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype=jnp.float32).astype(dtype) * std}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p


def dense(p: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    y = jnp.dot(x.astype(compute_dtype), p["w"].astype(compute_dtype))
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


ACTS = {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}


def init_mlp(key, d: int, d_ff: int, glu: bool, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"up": init_dense(ks[0], d, d_ff, dtype),
         "down": init_dense(ks[1], d_ff, d, dtype)}
    if glu:
        p["gate"] = init_dense(ks[2], d, d_ff, dtype)
    return p


def mlp(p: dict, x: jnp.ndarray, act: str, glu: bool, compute_dtype) -> jnp.ndarray:
    h = dense(p["up"], x, compute_dtype)
    if glu:
        h = ACTS[act](dense(p["gate"], x, compute_dtype)) * h
    else:
        h = ACTS[act](h)
    return dense(p["down"], h, compute_dtype)


# ------------------------------------------------- chunked flash attention
#
# Pure-JAX blockwise online-softmax attention (the XLA reference path; the
# Pallas kernel in repro.kernels.flash_attention is the TPU hot path).
# Causal masking is applied per block; the XLA path pays full O(S^2) FLOPs
# (block skipping happens in the Pallas kernel).

NEG_INF = -1e30


def _gqa_scores(q, k, compute_dtype):
    """q [B,Sq,KVH,G,D] x k [B,Skv,KVH,D] -> [B,KVH,G,Sq,Skv] fp32."""
    return jnp.einsum("bskgd,btkd->bkgst", q.astype(compute_dtype),
                      k.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, chunk_q: int, chunk_kv: int,
                      q_positions: Optional[jnp.ndarray] = None,
                      kv_positions: Optional[jnp.ndarray] = None,
                      scale: Optional[float] = None,
                      compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """GQA attention with blockwise online softmax.

    q: [B, Sq, H, Dq]   k: [B, Skv, KVH, Dq]   v: [B, Skv, KVH, Dv]
    returns [B, Sq, H, Dv].
    """
    B, Sq, H, Dq = q.shape
    _, Skv, KVH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dq)
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :].repeat(B, 0)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)[None, :].repeat(B, 0)

    def _pick(S, c):
        c = min(c, S) if c else S
        while S % c:
            c -= 1
        return c

    cq = _pick(Sq, chunk_q)
    ck = _pick(Skv, chunk_kv)
    nq, nk = Sq // cq, Skv // ck

    qg = q.reshape(B, nq, cq, KVH, G, Dq)
    kg = k.reshape(B, nk, ck, KVH, Dq)
    vg = v.reshape(B, nk, ck, KVH, Dv)
    qpos = q_positions.reshape(B, nq, cq)
    kpos = kv_positions.reshape(B, nk, ck)

    def q_block(args):
        qi, qpi = args                                     # [B,cq,KVH,G,Dq], [B,cq]

        def kv_step(carry, blk):
            o, m, l = carry
            kj, vj, kpj = blk                              # [B,ck,KVH,Dq], ...
            s = _gqa_scores(qi, kj, compute_dtype) * scale  # [B,KVH,G,cq,ck] f32
            if causal:
                mask = qpi[:, None, None, :, None] >= kpj[:, None, None, None, :]
                s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))         # [B,KVH,G,cq]
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(compute_dtype),
                            vj.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
            o_new = o * corr[..., None] + pv
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((B, KVH, G, cq, Dv), jnp.float32)
        m0 = jnp.full((B, KVH, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, cq), jnp.float32)
        (o, m, l), _ = jax.lax.scan(
            kv_step, (o0, m0, l0),
            (kg.swapaxes(0, 1), vg.swapaxes(0, 1), kpos.swapaxes(0, 1)))
        o = o / jnp.maximum(l[..., None], 1e-30)
        return o.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, Dv)

    out = jax.lax.map(q_block, (qg.swapaxes(0, 1), qpos.swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(B, Sq, H, Dv).astype(q.dtype)


def full_attention(q, k, v, *, causal, q_positions=None, kv_positions=None,
                   scale=None, compute_dtype=jnp.bfloat16):
    """Unchunked reference attention (small shapes / oracles)."""
    B, Sq, H, Dq = q.shape
    _, Skv, KVH, Dv = *k.shape[:3], v.shape[-1]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dq)
    qg = q.reshape(B, Sq, KVH, G, Dq)
    s = _gqa_scores(qg, k, compute_dtype) * scale          # [B,KVH,G,Sq,Skv]
    if causal:
        if q_positions is None:
            q_positions = jnp.arange(Sq)[None, :].repeat(B, 0)
        if kv_positions is None:
            kv_positions = jnp.arange(Skv)[None, :].repeat(B, 0)
        mask = q_positions[:, None, None, :, None] >= kv_positions[:, None, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p.astype(compute_dtype),
                   v.astype(compute_dtype), preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, Dv).astype(q.dtype)


def softmax_with_token(s, s_new):
    """Softmax over ``s [..., S]`` with one more column ``s_new [..., 1]``
    after it, as the two parts ``(p [..., S], p_new [..., 1])``: a token's
    attention over a cache and over itself, without joining the two."""
    m = jnp.maximum(s.max(axis=-1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    z = e.sum(axis=-1, keepdims=True) + e_new
    return e / z, e_new / z


def decode_attention(q, k_cache, v_cache, lengths, *, k_new=None, v_new=None,
                     scale=None, compute_dtype=jnp.bfloat16):
    """One-token attention against a KV cache.

    q: [B, 1, H, D]; k/v_cache: [B, Smax, KVH, D*]; lengths: [B] valid length
    (the new token's position is lengths-1 after cache insert). With
    ``k_new``/``v_new`` [B, KVH, D*], the token's own key and value not yet
    in the cache, it attends over the cache's first ``lengths`` positions
    and over itself.
    """
    B, _, H, Dq = q.shape
    Smax, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dq)
    qg = q.reshape(B, 1, KVH, G, Dq)
    s = _gqa_scores(qg, k_cache, compute_dtype) * scale    # [B,KVH,G,1,Smax]
    valid = jnp.arange(Smax)[None, :] < lengths[:, None]   # [B,Smax]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)

    def readout(p, v):
        return jnp.einsum("bkgst,btkd->bskgd", p.astype(compute_dtype),
                          v.astype(compute_dtype),
                          preferred_element_type=jnp.float32)

    if k_new is None:
        o = readout(jax.nn.softmax(s, axis=-1), v_cache)
    else:
        s_new = _gqa_scores(qg, k_new[:, None], compute_dtype) * scale
        p, p_new = softmax_with_token(s, s_new)
        o = readout(p, v_cache) + readout(p_new, v_new[:, None])
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ------------------------------------------------------------- embeddings

def init_embedding(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


def unembed(x: jnp.ndarray, emb_or_w: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    """x [B,S,d] @ W [V,d]^T -> fp32 logits."""
    return jnp.einsum("bsd,vd->bsv", x.astype(compute_dtype),
                      emb_or_w.astype(compute_dtype),
                      preferred_element_type=jnp.float32)
