"""Attention blocks: GQA (with optional QK-norm / bias) and DeepSeek MLA.

Each block exposes:
  init(key, cfg, dtype) -> params
  forward(params, x, cfg, positions) -> y                  (full sequence)
  init_cache(cfg, batch, max_seq, dtype) -> cache
  prefill(params, x, cfg, cache, positions) -> (y, cache)  (writes cache)
  decode_token(params, x, cfg, cache, lengths) -> (y, new) (x is [B,1,d];
      ``new`` is the token's cache entries, which the caller writes;
      ``cache`` is one layer's cache or a sequence of them, one per group
      of rows, as ``row_groups`` splits them)
  decode(params, x, cfg, cache, lengths) -> (y, cache)     (GQA: the same,
      written into one layer's cache, for the hybrid and enc-dec models)

MLA caches the compressed latent (c_kv + k_rope) and uses the absorbed
matmul form for decode (W_uk folded into q, W_uv applied post-attention),
so decode cost is O(S * kv_lora) per head rather than O(S * head_dims)
after decompression. ``decode_naive`` keeps the decompressing variant as
a cross-check oracle (see tests/test_mla.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MLAConfig
from repro.models.layers import (
    apply_rope, chunked_attention, decode_attention, dense, dt, init_dense,
    rmsnorm, softmax_with_token, yarn_mscale,
)

# =========================================================== GQA attention


def gqa_init(key, cfg: ArchConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], d, cfg.n_heads * hd, dtype, bias=cfg.qkv_bias),
        "wk": init_dense(ks[1], d, cfg.kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wv": init_dense(ks[2], d, cfg.kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wo": init_dense(ks[3], cfg.n_heads * hd, d, dtype),
    }
    if getattr(cfg, "qk_norm", False):
        p["q_scale"] = jnp.ones((hd,), dtype=dtype)
        p["k_scale"] = jnp.ones((hd,), dtype=dtype)
    return p


def _qkv(p, x, cfg: ArchConfig, positions, project=dense):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    cdt = dt(cfg.compute_dtype)
    q = project(p["wq"], x, cdt).reshape(B, S, cfg.n_heads, hd)
    k = project(p["wk"], x, cdt).reshape(B, S, cfg.kv_heads, hd)
    v = project(p["wv"], x, cdt).reshape(B, S, cfg.kv_heads, hd)
    if "q_scale" in p:
        q = rmsnorm(q, p["q_scale"])
        k = rmsnorm(k, p["k_scale"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, x, cfg: ArchConfig, positions, causal=True):
    cdt = dt(cfg.compute_dtype)
    q, k, v = _qkv(p, x, cfg, positions)
    o = chunked_attention(q, k, v, causal=causal,
                          chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
                          q_positions=positions, kv_positions=positions,
                          compute_dtype=cdt)
    B, S = x.shape[:2]
    return dense(p["wo"], o.reshape(B, S, -1), cdt)


def gqa_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> dict:
    hd = cfg.resolved_head_dim
    shp = (batch, max_seq, cfg.kv_heads, hd)
    return {"k": jnp.zeros(shp, dtype=dtype), "v": jnp.zeros(shp, dtype=dtype)}


def gqa_prefill(p, x, cfg: ArchConfig, cache, positions):
    """Full-sequence forward that also fills cache[:, :S]."""
    q, k, v = _qkv(p, x, cfg, positions)
    S = x.shape[1]
    cache = {"k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
             "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, axis=1)}
    cdt = dt(cfg.compute_dtype)
    o = chunked_attention(q, k, v, causal=True,
                          chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
                          q_positions=positions, kv_positions=positions,
                          compute_dtype=cdt)
    B = x.shape[0]
    return dense(p["wo"], o.reshape(B, S, -1), cdt), cache


def write_tokens(cache, new, lengths, stacked: bool = False):
    """``cache`` with row b of each buffer set at position ``lengths[b]``
    to row b of ``new``'s, and nothing else changed. The buffers are one
    layer's ``[B,S,...]`` with ``new`` ``[B,...]``, or, ``stacked``,
    every layer's ``[L,B,S,...]`` with ``new`` ``[L,B,...]``."""
    at = (jnp.arange(lengths.shape[0]), lengths)
    if stacked:
        at = (slice(None), *at)
    return {k: buf.at[at].set(new[k].astype(buf.dtype))
            for k, buf in cache.items()}


def row_groups(cache, stacked: bool = False) -> list:
    """``cache``, one cache ``{name: [B,...]}`` (``stacked``: ``[L,B,...]``)
    or a sequence of them, as ``[(rows, cache)]``: each cache with the
    slice of the step's rows it holds, the caches' batches stacked in the
    sequence's order."""
    caches = [cache] if isinstance(cache, dict) else cache
    out, at = [], 0
    for c in caches:
        n = next(iter(c.values())).shape[int(stacked)]
        out.append((slice(at, at + n), c))
        at += n
    return out


def decode_dense(p, x, cdt):
    """``dense`` for a decode round's rows, its ``[B,1,n]`` output pinned
    as the matmul lays it out. Attention reads a projection head-major;
    left free, the compiler from two rows on has the matmul write that
    layout, and to do so copies and transposes the weight, in every layer
    of every call. Pinned, the reshape to heads moves the B·n outputs."""
    return jax.lax.optimization_barrier(dense(p, x, cdt))


def gqa_decode_token(p, x, cfg: ArchConfig, cache, lengths):
    """x: [B,1,d]; lengths[b] = number of tokens BEFORE this one. Attends
    over the cache's first ``lengths[b]`` positions and the token itself,
    and returns ``(y, new)``: the token's ``{"k", "v"}`` ``[B,KVH,D]``,
    which it leaves to the caller to write (``write_tokens``). The
    projections run once over the batch; each row attends over its own
    cache of ``row_groups(cache)``."""
    B = x.shape[0]
    cdt = dt(cfg.compute_dtype)
    groups = row_groups(cache)
    positions = lengths[:, None]                            # [B,1]
    q, k, v = _qkv(p, x, cfg, positions, decode_dense)
    kv_dt = groups[0][1]["k"].dtype
    new = {"k": k[:, 0].astype(kv_dt), "v": v[:, 0].astype(kv_dt)}
    o = jnp.concatenate([
        decode_attention(q[r], c["k"], c["v"], lengths[r], k_new=new["k"][r],
                         v_new=new["v"][r], compute_dtype=cdt)
        for r, c in groups])
    return dense(p["wo"], o.reshape(B, 1, -1), cdt), new


def gqa_decode(p, x, cfg: ArchConfig, cache, lengths):
    """``gqa_decode_token`` on one layer's cache, with the token written."""
    y, new = gqa_decode_token(p, x, cfg, cache, lengths)
    return y, write_tokens(cache, new, lengths)


# =========================================================== MLA attention


def mla_init(key, cfg: ArchConfig, dtype) -> dict:
    m = cfg.mla or MLAConfig()
    d, H = cfg.d_model, cfg.n_heads
    qk = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": init_dense(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), dtype=dtype)},
        "wq_b": init_dense(ks[1], m.q_lora_rank, H * qk, dtype),
        "wkv_a": init_dense(ks[2], d, m.kv_lora_rank + m.rope_head_dim, dtype),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dtype=dtype)},
        "wkv_b": init_dense(ks[3], m.kv_lora_rank,
                            H * (m.nope_head_dim + m.v_head_dim), dtype),
        "wo": init_dense(ks[4], H * m.v_head_dim, d, dtype),
    }


def _mla_rope(x, positions, cfg):
    """Rotary on interleaved pairs of the rope dims, as DeepSeek's code
    applies it."""
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling,
                      interleaved=True)


def mla_scale(cfg: ArchConfig) -> float:
    """The softmax scale: 1/sqrt(qk head size), times YaRN's mscale
    squared where the rotary is YaRN-scaled."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    if cfg.rope_scaling is not None:
        scale *= yarn_mscale(cfg.rope_scaling) ** 2
    return scale


def _mla_q(p, x, cfg, positions, project=dense):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cdt = dt(cfg.compute_dtype)
    qa = rmsnorm(dense(p["wq_a"], x, cdt), p["q_norm"]["scale"], cfg.norm_eps)
    q = project(p["wq_b"], qa, cdt).reshape(B, S, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    return q_nope, _mla_rope(q_rope, positions, cfg)


def _mla_latent(p, x, cfg, positions):
    m = cfg.mla
    cdt = dt(cfg.compute_dtype)
    kv_a = dense(p["wkv_a"], x, cdt)                        # [B,S,lora+rope]
    c_kv = rmsnorm(kv_a[..., :m.kv_lora_rank], p["kv_norm"]["scale"],
                   cfg.norm_eps)
    k_rope = _mla_rope(kv_a[..., None, m.kv_lora_rank:], positions,
                       cfg)[..., 0, :]                      # [B,S,rope] shared
    return c_kv, k_rope


def _mla_attend(p, x, cfg, positions, c_kv, k_rope, causal=True):
    """Decompressed attention of ``x``'s queries over the latents."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cdt = dt(cfg.compute_dtype)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    kv = dense(p["wkv_b"], c_kv, cdt).reshape(B, S, H, m.nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = chunked_attention(q, k, v, causal=causal,
                          chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
                          q_positions=positions, kv_positions=positions,
                          scale=mla_scale(cfg), compute_dtype=cdt)
    return dense(p["wo"], o.reshape(B, S, -1), cdt)


def mla_forward(p, x, cfg: ArchConfig, positions, causal=True):
    with jax.named_scope("mla"):
        c_kv, k_rope = _mla_latent(p, x, cfg, positions)
        return _mla_attend(p, x, cfg, positions, c_kv, k_rope, causal)


def mla_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> dict:
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype=dtype),
            "k_rope": jnp.zeros((batch, max_seq, m.rope_head_dim), dtype=dtype)}


def mla_prefill(p, x, cfg: ArchConfig, cache, positions):
    with jax.named_scope("mla"):
        c_kv, k_rope = _mla_latent(p, x, cfg, positions)
        cache = {"c_kv": jax.lax.dynamic_update_slice_in_dim(
                     cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1),
                 "k_rope": jax.lax.dynamic_update_slice_in_dim(
                     cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0, axis=1)}
        return _mla_attend(p, x, cfg, positions, c_kv, k_rope), cache


# rows of a matrix that one of the chip's (8, 128) tiles holds
TILE_ROWS = 8


def _mla_wkv_b_tiles(p, cfg):
    """``wkv_b`` ``[lora, H·(nope+v)]`` as ``[lora/8, H, 8, nope+v]``,
    split into ``W_uk`` and ``W_uv``: the order in which the chip's tiles
    hold its elements, so that read this way it is not relaid out."""
    m = cfg.mla
    w = p["wkv_b"]["w"].reshape(m.kv_lora_rank // TILE_ROWS, TILE_ROWS,
                                cfg.n_heads, m.nope_head_dim + m.v_head_dim)
    w = w.transpose(0, 2, 1, 3)
    return w[..., :m.nope_head_dim], w[..., m.nope_head_dim:]


def mla_decode_token(p, x, cfg: ArchConfig, cache, lengths):
    """Absorbed-form decode: score/readout in the compressed latent space,
    over the cache's first ``lengths[b]`` positions and the token itself.
    Returns ``(y, new)`` as ``gqa_decode_token`` does, ``new`` the token's
    ``{"c_kv": [B,lora], "k_rope": [B,rope]}``; as there, each row reads
    its own cache of ``row_groups(cache)``."""
    with jax.named_scope("mla"):
        return _mla_decode_token(p, x, cfg, cache, lengths)


def _mla_decode_token(p, x, cfg: ArchConfig, cache, lengths):
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    cdt = dt(cfg.compute_dtype)
    positions = lengths[:, None]
    groups = row_groups(cache)
    q_nope, q_rope = _mla_q(p, x, cfg, positions, decode_dense)  # [B,1,H,*]
    c_kv_new, k_rope_new = _mla_latent(p, x, cfg, positions)
    new = {"c_kv": c_kv_new[:, 0].astype(groups[0][1]["c_kv"].dtype),
           "k_rope": k_rope_new[:, 0].astype(groups[0][1]["k_rope"].dtype)}
    w_uk, w_uv = _mla_wkv_b_tiles(p, cfg)                  # [t,H,8,nope|v]
    f32 = jnp.float32

    # The absorbed matmuls, one row at a time and as products and sums over
    # wkv_b's tiles: an einsum has the compiler relay wkv_b out head-major
    # for a row, and transpose it for a batch of rows, in every layer.
    def absorb(qn):                                         # [1,H,nope]
        s = jnp.sum(qn[:, None, :, None].astype(cdt).astype(f32)
                    * w_uk.astype(cdt).astype(f32), -1)      # [1,t,H,8]
        return s.transpose(0, 2, 1, 3).reshape(1, H, -1)    # [1,H,lora]

    def unabsorb(ol):                                       # [1,H,lora]
        ol = ol.astype(cdt).astype(f32).reshape(1, H, -1, TILE_ROWS)
        return jnp.sum(ol.transpose(0, 2, 1, 3)[..., None]
                       * w_uv.astype(cdt).astype(f32), (1, 3))  # [1,H,v]

    q_lat = jnp.concatenate([absorb(q_nope[i:i + 1, 0])
                             for i in range(B)])[:, None]   # [B,1,H,lora]

    def scores(r, ckv, krp):                                # -> [b,H,1,T]
        return (jnp.einsum("bshl,btl->bhst", q_lat[r].astype(cdt),
                           ckv.astype(cdt), preferred_element_type=jnp.float32)
                + jnp.einsum("bshr,btr->bhst", q_rope[r].astype(cdt),
                             krp.astype(cdt),
                             preferred_element_type=jnp.float32)
                ) * mla_scale(cfg)

    def readout(pattn, c):                                  # -> [b,1,H,lora]
        return jnp.einsum("bhst,btl->bshl", pattn.astype(cdt), c.astype(cdt),
                          preferred_element_type=jnp.float32)

    def attend(r, ckv, krp):                                # one group's rows
        Smax = ckv.shape[1]
        valid = jnp.arange(Smax)[None, :] < lengths[r][:, None]
        s = jnp.where(valid[:, None, None, :], scores(r, ckv, krp), -1e30)
        c_new = new["c_kv"][r][:, None]
        pattn, p_new = softmax_with_token(
            s, scores(r, c_new, new["k_rope"][r][:, None]))
        return readout(pattn, ckv) + readout(p_new, c_new)

    o_lat = jnp.concatenate([attend(r, c["c_kv"], c["k_rope"])
                             for r, c in groups])
    o = jnp.concatenate([unabsorb(o_lat[i:i + 1, 0])
                         for i in range(B)])[:, None]       # [B,1,H,v]
    y = dense(p["wo"], o.reshape(B, 1, H * m.v_head_dim).astype(cdt), cdt)
    return y, new


def mla_decode_naive(p, x, cfg: ArchConfig, cache, lengths):
    """Decompress-then-attend decode (oracle for the absorbed form)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    cdt = dt(cfg.compute_dtype)
    positions = lengths[:, None]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv_new, k_rope_new = _mla_latent(p, x, cfg, positions)
    bidx = jnp.arange(B)
    ckv = cache["c_kv"].at[bidx, lengths, :].set(c_kv_new[:, 0].astype(cache["c_kv"].dtype))
    krp = cache["k_rope"].at[bidx, lengths, :].set(k_rope_new[:, 0].astype(cache["k_rope"].dtype))
    kv = dense(p["wkv_b"], ckv.astype(cdt), cdt)
    Smax = ckv.shape[1]
    kv = kv.reshape(B, Smax, H, m.nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krp[:, :, None, :].astype(cdt), (B, Smax, H, m.rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = mla_scale(cfg)
    o = decode_attention(q, k, v, lengths + 1, scale=scale, compute_dtype=cdt)
    y = dense(p["wo"], o.reshape(B, 1, -1), cdt)
    return y, {"c_kv": ckv, "k_rope": krp}
