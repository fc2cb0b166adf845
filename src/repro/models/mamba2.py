"""Mamba2 (SSD) block: chunked parallel forward + recurrent decode.

Faithful to the SSD formulation (Dao & Gu 2024): per-head scalar decay
A, dt via softplus, depthwise causal conv on (x, B, C), gated output with
RMSNorm. The chunked scan carries the inter-chunk state h [B, nh, hd, N]
so the forward is O(S·Q) memory instead of O(S^2).

Decode keeps (conv window, h state) per layer — constant size, which is
what makes zamba2/long_500k runnable: the state does not grow with the
context.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import dt as _dt, rmsnorm


def _dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, nh, conv_ch


def mamba2_init(key, cfg: ArchConfig, dtype) -> dict:
    s, d_in, nh, conv_ch = _dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    proj_out = 2 * d_in + 2 * s.n_groups * s.d_state + nh
    std = 1.0 / math.sqrt(d)
    # dt bias init: softplus^-1 of uniform in [1e-3, 1e-1]
    u = jax.random.uniform(ks[2], (nh,), minval=math.log(1e-3), maxval=math.log(1e-1))
    dt0 = jnp.exp(u)
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "in_proj": (jax.random.normal(ks[0], (d, proj_out)) * std).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_kernel, conv_ch)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype=dtype),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "out_norm": jnp.ones((d_in,), dtype=dtype),
        "out_proj": (jax.random.normal(ks[3], (d_in, d)) / math.sqrt(d_in)).astype(dtype),
    }


def _split_proj(p, x, cfg, cdt):
    s, d_in, nh, _ = _dims(cfg)
    z = jnp.dot(x.astype(cdt), p["in_proj"].astype(cdt))
    gz, xc, Bc, Cc, dtr = jnp.split(
        z, [d_in, 2 * d_in, 2 * d_in + s.n_groups * s.d_state,
            2 * d_in + 2 * s.n_groups * s.d_state], axis=-1)
    return gz, xc, Bc, Cc, dtr


def _conv_full(p, u, cfg):
    """Depthwise causal conv over [B, S, C]."""
    K = cfg.ssm.conv_kernel
    uf = u.astype(jnp.float32)
    pad = jnp.pad(uf, ((0, 0), (K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)                     # [K, C]
    out = sum(pad[:, i:i + u.shape[1], :] * w[i] for i in range(K))
    return jax.nn.silu(out + p["conv_b"].astype(jnp.float32)).astype(u.dtype)


def _ssd_chunk_scan(xh, dtv, A, Bm, Cm, h0, chunk):
    """Chunked SSD. xh [B,S,nh,hd]; dtv [B,S,nh] (post-softplus);
    A [nh] (negative); Bm/Cm [B,S,G,N]. Returns (y [B,S,nh,hd], h_final)."""
    Bsz, S, nh, hd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    while S % Q:
        Q -= 1
    nc = S // Q
    rep = nh // G

    def to_chunks(a):
        return a.reshape(Bsz, nc, Q, *a.shape[2:]).swapaxes(0, 1)

    xc, dtc, Bc, Cc = map(to_chunks, (xh, dtv, Bm, Cm))

    def step(h, blk):
        xq, dtq, Bq, Cq = blk                               # [B,Q,...]
        dtA = dtq * A                                       # [B,Q,nh] (<=0)
        cums = jnp.cumsum(dtA, axis=1)                      # inclusive
        Bh = jnp.repeat(Bq, rep, axis=2)                    # [B,Q,nh,N]
        Ch = jnp.repeat(Cq, rep, axis=2)
        xdt = xq * dtq[..., None]                           # [B,Q,nh,hd]
        # intra-chunk
        CB = jnp.einsum("bihn,bjhn->bhij", Ch, Bh)          # [B,nh,Q,Q]
        seg = cums[:, :, None, :] - cums[:, None, :, :]     # [B,i,j,nh]
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        att = CB * L.transpose(0, 3, 1, 2)                  # [B,nh,i,j]
        y = jnp.einsum("bhij,bjhp->bihp", att, xdt)
        # inter-chunk (state from previous chunks)
        y = y + jnp.einsum("bihn,bhpn->bihp",
                           Ch * jnp.exp(cums)[..., None], h)
        # state update
        dec_end = jnp.exp(cums[:, -1:, :] - cums)           # [B,Q,nh]
        h_new = jnp.exp(cums[:, -1])[:, :, None, None] * h + \
            jnp.einsum("bjhp,bjhn->bhpn", xdt * dec_end[..., None], Bh)
        return h_new, y

    h_fin, ys = jax.lax.scan(step, h0, (xc, dtc, Bc, Cc))
    y = ys.swapaxes(0, 1).reshape(Bsz, S, nh, hd)
    return y, h_fin


def mamba2_forward(p, x, cfg: ArchConfig, h0=None):
    """x [B,S,d] -> (y [B,S,d], h_final). fp32 SSD core."""
    s, d_in, nh, conv_ch = _dims(cfg)
    cdt = _dt(cfg.compute_dtype)
    Bsz, S, _ = x.shape
    gz, xc, Bc, Cc, dtr = _split_proj(p, x, cfg, cdt)
    u = jnp.concatenate([xc, Bc, Cc], axis=-1)
    u = _conv_full(p, u, cfg)
    xc, Bc, Cc = jnp.split(u, [d_in, d_in + s.n_groups * s.d_state], axis=-1)
    xh = xc.reshape(Bsz, S, nh, s.head_dim).astype(jnp.float32)
    Bm = Bc.reshape(Bsz, S, s.n_groups, s.d_state).astype(jnp.float32)
    Cm = Cc.reshape(Bsz, S, s.n_groups, s.d_state).astype(jnp.float32)
    dtv = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, s.head_dim, s.d_state), jnp.float32)
    y, h_fin = _ssd_chunk_scan(xh, dtv, A, Bm, Cm, h0, s.chunk)
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(Bsz, S, d_in)
    y = y * jax.nn.silu(gz.astype(jnp.float32))
    y = rmsnorm(y.astype(cdt), p["out_norm"])
    return jnp.dot(y, p["out_proj"].astype(cdt)), h_fin


def mamba2_init_state(cfg: ArchConfig, batch: int) -> dict:
    s, d_in, nh, conv_ch = _dims(cfg)
    return {"conv": jnp.zeros((batch, s.conv_kernel - 1, conv_ch), jnp.float32),
            "h": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32)}


def mamba2_prefill(p, x, cfg: ArchConfig, state):
    """Forward that also produces the decode state at the end of x."""
    s, d_in, nh, conv_ch = _dims(cfg)
    cdt = _dt(cfg.compute_dtype)
    gz, xc, Bc, Cc, dtr = _split_proj(p, x, cfg, cdt)
    u = jnp.concatenate([xc, Bc, Cc], axis=-1)
    K = s.conv_kernel
    conv_state = u[:, -(K - 1):, :].astype(jnp.float32) if x.shape[1] >= K - 1 \
        else jnp.pad(u.astype(jnp.float32), ((0, 0), (K - 1 - x.shape[1], 0), (0, 0)))
    y, h_fin = mamba2_forward(p, x, cfg, h0=state["h"])
    return y, {"conv": conv_state, "h": h_fin}


def mamba2_decode(p, x, cfg: ArchConfig, state):
    """x [B,1,d] single-step recurrence."""
    s, d_in, nh, conv_ch = _dims(cfg)
    cdt = _dt(cfg.compute_dtype)
    Bsz = x.shape[0]
    gz, xc, Bc, Cc, dtr = _split_proj(p, x, cfg, cdt)
    u = jnp.concatenate([xc, Bc, Cc], axis=-1)[:, 0, :]     # [B, conv_ch]
    window = jnp.concatenate([state["conv"], u[:, None, :].astype(jnp.float32)], 1)
    w = p["conv_w"].astype(jnp.float32)
    conv_out = jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"].astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out)
    xc1, Bc1, Cc1 = jnp.split(conv_out, [d_in, d_in + s.n_groups * s.d_state], -1)
    xh = xc1.reshape(Bsz, nh, s.head_dim)
    Bm = jnp.repeat(Bc1.reshape(Bsz, s.n_groups, s.d_state), nh // s.n_groups, 1)
    Cm = jnp.repeat(Cc1.reshape(Bsz, s.n_groups, s.d_state), nh // s.n_groups, 1)
    dtv = jax.nn.softplus(dtr[:, 0].astype(jnp.float32) + p["dt_bias"])  # [B,nh]
    A = -jnp.exp(p["A_log"])
    dec = jnp.exp(dtv * A)                                  # [B,nh]
    h = dec[:, :, None, None] * state["h"] + \
        jnp.einsum("bhp,bhn->bhpn", xh * dtv[..., None], Bm)
    y = jnp.einsum("bhn,bhpn->bhp", Cm, h) + p["D"][None, :, None] * xh
    y = y.reshape(Bsz, 1, d_in) * jax.nn.silu(gz.astype(jnp.float32))
    y = rmsnorm(y.astype(cdt), p["out_norm"])
    y = jnp.dot(y, p["out_proj"].astype(cdt))
    return y, {"conv": window[:, 1:, :], "h": h}
