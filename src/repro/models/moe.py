"""Mixture-of-Experts FFN with expert parallelism.

Three dispatch strategies, all numerically equivalent up to capacity
drops (tested against each other):

  * ``local``      — no mesh (smoke tests): capacity-bucketed batched
                     matmul on one device.
  * ``a2a``        — shard_map expert parallelism: tokens split over the
                     model axis, bucketed per destination expert shard,
                     exchanged with ``lax.all_to_all``, expert-batched
                     matmuls, reverse a2a, weighted combine at the source,
                     all_gather to re-replicate. Used for train/prefill
                     (many tokens per device).
  * ``replicated`` — every model shard routes the full local token set and
                     computes only its own experts; partial outputs are
                     psum'd. No a2a; right for tiny decode batches.

Expert-count < model-axis handling (grok: 8 experts on 16 shards): the
expert hidden dim is split tp_e = M/E ways and each token is dispatched to
all tp_e shards of its expert group; the partial FFN outputs simply add in
the source-side combine (no extra collective). Weight layout is therefore
device-major: ``[M, Epg, d, ffl]`` — see ``expert_layout``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import ACTS, dt


@dataclass(frozen=True)
class ExpertLayout:
    M: int          # model-axis size (1 = no mesh)
    ep: int         # expert-parallel degree (= gcd(E, M))
    tp_e: int       # tensor-parallel ways within an expert (= M // ep)
    epg: int        # experts per ep group (= E // ep)
    ffl: int        # local expert hidden dim (= d_ff_e // tp_e)


def expert_layout(cfg: ArchConfig, model_size: int) -> ExpertLayout:
    E = cfg.moe.n_experts
    M = max(model_size, 1)
    ep = math.gcd(E, M)
    tp_e = M // ep
    if E % ep or M % ep:
        raise ValueError(f"cannot lay out {E} experts on model axis {M}")
    ffe = cfg.moe.d_ff or cfg.d_ff
    if ffe % tp_e:
        raise ValueError(f"expert d_ff {ffe} not divisible by tp_e {tp_e}")
    return ExpertLayout(M=M, ep=ep, tp_e=tp_e, epg=E // ep, ffl=ffe // tp_e)


def moe_init(key, cfg: ArchConfig, dtype, model_size: int) -> dict:
    """Device-major expert weights: [M, Epg, d, ffl] / [M, Epg, ffl, d]."""
    lay = expert_layout(cfg, model_size)
    d = cfg.d_model
    E = cfg.moe.n_experts
    ks = jax.random.split(key, 6)
    std = 1.0 / math.sqrt(d)
    std_ff = 1.0 / math.sqrt(lay.ffl * lay.tp_e)

    def w(k, shape, s):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * s).astype(dtype)

    p = {
        "router": w(ks[0], (d, E), std),
        "up": w(ks[1], (lay.M, lay.epg, d, lay.ffl), std),
        "down": w(ks[2], (lay.M, lay.epg, lay.ffl, d), std_ff),
    }
    if cfg.glu:
        p["gate"] = w(ks[3], (lay.M, lay.epg, d, lay.ffl), std)
    if cfg.moe.n_shared:
        ffe = (cfg.moe.d_ff or cfg.d_ff) * cfg.moe.n_shared
        p["shared"] = {
            "up": w(ks[4], (d, ffe), std),
            "down": w(ks[5], (ffe, d), 1.0 / math.sqrt(ffe)),
        }
        if cfg.glu:
            p["shared"]["gate"] = w(jax.random.fold_in(ks[4], 1), (d, ffe), std)
    return p


def moe_param_specs(cfg: ArchConfig, dist) -> dict:
    """PartitionSpecs matching moe_init's layout.

    Expert weights shard on the device-major EP dim ('model') AND — when
    FSDP is on — over the dp axes on the d dim; the shard_map body
    all-gathers the d dim on use (ZeRO-3 semantics; the AD transpose of
    that gather is the gradient reduce-scatter). Router and shared expert
    are small and replicated."""
    ep = dist.ep_axes if dist.active else None
    if dist.ep_over_dp:
        fs = None          # experts fully sharded by EP itself
    else:
        fs = dist.dp_axes if (dist.fsdp and dist.dp_axes) else None
    specs = {
        "router": P(None, None),
        "up": P(ep, None, fs, None),
        "down": P(ep, None, None, fs),
    }
    if cfg.glu:
        specs["gate"] = P(ep, None, fs, None)
    if cfg.moe.n_shared:
        specs["shared"] = {"up": P(None, None), "down": P(None, None)}
        if cfg.glu:
            specs["shared"]["gate"] = P(None, None)
    return specs


def _gather_experts(p, dist):
    """Inside shard_map: reconstruct full [Epg, d, ffl] expert blocks by
    all-gathering the FSDP-sharded dim over the dp axes. With ep_over_dp
    the weights are already fully local (no FSDP dim)."""
    if dist.ep_over_dp or not (dist.fsdp and dist.dp_axes):
        return {k: (p[k][0] if k in ("up", "down", "gate") else p[k])
                for k in p}
    ax = dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]
    out = dict(p)
    out["up"] = jax.lax.all_gather(p["up"][0], ax, axis=1, tiled=True)
    if "gate" in p:
        out["gate"] = jax.lax.all_gather(p["gate"][0], ax, axis=1, tiled=True)
    out["down"] = jax.lax.all_gather(p["down"][0], ax, axis=2, tiled=True)
    return out


# ------------------------------------------------------------ primitives


def _route(x, router_w, cfg: ArchConfig):
    """Returns (weights [T,k] f32, ids [T,k] i32, aux dict)."""
    moe = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, moe.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance loss (Switch-style) + router z-loss, local means
    me = probs.mean(0)                                     # [E]
    ce = jnp.zeros((moe.n_experts,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / (ids.size))                                  # fraction routed
    lb = moe.n_experts * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return w, ids, {"lb_loss": lb, "z_loss": z}


def _expert_ffn(hbuf, p_gate, p_up, p_down, act: str, glu: bool, cdt):
    """hbuf [E?, C, d] x per-expert weights [E?, d, ffl] -> [E?, C, d]."""
    h = jnp.einsum("ecd,edf->ecf", hbuf.astype(cdt), p_up.astype(cdt))
    if glu:
        g = jnp.einsum("ecd,edf->ecf", hbuf.astype(cdt), p_gate.astype(cdt))
        h = ACTS[act](g) * h
    else:
        h = ACTS[act](h)
    return jnp.einsum("ecf,efd->ecd", h, p_down.astype(cdt))


def _shared_ffn(x, p, cfg, cdt):
    h = jnp.dot(x.astype(cdt), p["up"].astype(cdt))
    if cfg.glu:
        h = ACTS[cfg.act](jnp.dot(x.astype(cdt), p["gate"].astype(cdt))) * h
    else:
        h = ACTS[cfg.act](h)
    return jnp.dot(h, p["down"].astype(cdt))


# -------------------------------------------------------- local dispatch


def moe_local(p, x2, cfg: ArchConfig):
    """Single-device capacity-bucketed MoE; oracle for the sharded paths."""
    lay = expert_layout(cfg, 1)
    moe = cfg.moe
    cdt = dt(cfg.compute_dtype)
    T, d = x2.shape
    w, ids, aux = _route(x2, p["router"], cfg)
    E = moe.n_experts
    C = max(1, int(math.ceil(T * moe.top_k / E * moe.capacity_factor)))
    f_ids = ids.reshape(-1)                                 # [T*k]
    f_w = w.reshape(-1)
    f_tok = jnp.repeat(jnp.arange(T), moe.top_k)
    oh = jax.nn.one_hot(f_ids, E, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - 1)
    pos = jnp.take_along_axis(pos, f_ids[:, None], axis=1)[:, 0]
    valid = pos < C
    aux["drop_frac"] = 1.0 - valid.mean()
    buf = jnp.zeros((E, C, d), x2.dtype).at[f_ids, jnp.where(valid, pos, C)].set(
        x2[f_tok], mode="drop")
    # weights are stored device-major [M=1, Epg=E, ...]
    gate = p["gate"][0] if cfg.glu else None
    out_buf = _expert_ffn(buf, gate, p["up"][0], p["down"][0],
                          cfg.act, cfg.glu, cdt)
    rows = out_buf[f_ids, jnp.clip(pos, 0, C - 1)]          # [T*k, d]
    rows = rows * (valid[:, None] & True) * f_w[:, None]
    y = jnp.zeros((T, d), jnp.float32).at[f_tok].add(rows.astype(jnp.float32))
    if moe.n_shared:
        y = y + _shared_ffn(x2, p["shared"], cfg, cdt).astype(jnp.float32)
    return y.astype(x2.dtype), aux


# --------------------------------------------------- sharded: replicated


def _moe_replicated_body(p, x2, cfg: ArchConfig, lay: ExpertLayout, dist):
    """Every model shard holds all local tokens; computes own experts; psum."""
    model_axis = dist.model_axis
    moe = cfg.moe
    cdt = dt(cfg.compute_dtype)
    T, d = x2.shape
    pe = _gather_experts(p, dist)
    w, ids, aux = _route(x2, p["router"], cfg)
    midx = jax.lax.axis_index(model_axis) if model_axis else 0
    ep_rank = midx // lay.tp_e
    # global expert id range owned by this shard: [ep_rank*epg, ...)
    f_ids = ids.reshape(-1)
    f_w = w.reshape(-1)
    f_tok = jnp.repeat(jnp.arange(T), moe.top_k)
    local = f_ids // lay.epg == ep_rank                     # mine?
    l_ids = jnp.where(local, f_ids % lay.epg, lay.epg)      # epg = dump
    C = max(1, int(math.ceil(T * moe.top_k / max(lay.ep, 1)
                             * moe.capacity_factor)))
    oh = jax.nn.one_hot(l_ids, lay.epg + 1, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - 1, l_ids[:, None], 1)[:, 0]
    valid = local & (pos < C)
    buf = jnp.zeros((lay.epg, C, d), x2.dtype).at[
        jnp.where(valid, l_ids, lay.epg), jnp.where(valid, pos, C)].set(
        x2[f_tok], mode="drop")
    gate = pe["gate"] if cfg.glu else None
    out_buf = _expert_ffn(buf, gate, pe["up"], pe["down"],
                          cfg.act, cfg.glu, cdt)
    rows = out_buf[jnp.clip(l_ids, 0, lay.epg - 1), jnp.clip(pos, 0, C - 1)]
    rows = jnp.where(valid[:, None], rows, 0) * f_w[:, None].astype(rows.dtype)
    y = jnp.zeros((T, d), jnp.float32).at[f_tok].add(rows.astype(jnp.float32))
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    if moe.n_shared:
        y = y + _shared_ffn(x2, p["shared"], cfg, cdt).astype(jnp.float32)
    aux["drop_frac"] = 1.0 - (valid.sum() / jnp.maximum(local.sum(), 1))
    return y.astype(x2.dtype), aux


# ---------------------------------------------------------- sharded: a2a


def _moe_a2a_body(p, x2, cfg: ArchConfig, lay: ExpertLayout, dist):
    """Token-split + all_to_all EP; x2 is the dp-local token block,
    replicated over the model axis. With ep_over_dp the dispatch spans the
    full mesh (experts also sharded over the dp axes) while the token
    split stays per-model-rank — dp rows already hold distinct tokens."""
    model_axis = dist.model_axis
    ep_axes = dist.ep_axes
    moe = cfg.moe
    pe = _gather_experts(p, dist)
    cdt = dt(cfg.compute_dtype)
    M, tpe, epg = lay.M, lay.tp_e, lay.epg
    T, d = x2.shape
    midx = jax.lax.axis_index(model_axis)
    M_split = jax.lax.psum(1, model_axis)
    Tm = T // dist.model_size
    x_my = jax.lax.dynamic_slice_in_dim(x2, midx * Tm, Tm)  # [Tm, d]
    w, ids, aux = _route(x_my, p["router"], cfg)

    # flat entries: token x top-k x tp_e destinations
    f_ids = jnp.repeat(ids.reshape(-1), tpe)                # [Tm*k*tpe]
    f_w = jnp.repeat(w.reshape(-1), tpe)
    f_tok = jnp.repeat(jnp.repeat(jnp.arange(Tm), moe.top_k), tpe)
    tp_off = jnp.tile(jnp.arange(tpe), Tm * moe.top_k)
    dest = (f_ids // epg) * tpe + tp_off                    # destination device
    l_ids = f_ids % epg                                     # local expert at dest
    F = f_ids.shape[0]
    C = max(1, int(math.ceil(Tm * moe.top_k * tpe / M * moe.capacity_factor)))
    oh = jax.nn.one_hot(dest, M, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - 1, dest[:, None], 1)[:, 0]
    valid = pos < C
    aux["drop_frac"] = 1.0 - valid.mean()
    pos_s = jnp.where(valid, pos, C)
    send = jnp.zeros((M, C, d), x2.dtype).at[dest, pos_s].set(
        x_my[f_tok], mode="drop")
    meta = jnp.full((M, C), epg, jnp.int32).at[dest, pos_s].set(
        l_ids, mode="drop")                                 # epg = empty slot
    a2a_axis = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    recv = jax.lax.all_to_all(send, a2a_axis, 0, 0, tiled=True)
    rmeta = jax.lax.all_to_all(meta[..., None], a2a_axis, 0, 0,
                               tiled=True)[..., 0]
    rows = recv.reshape(M * C, d)
    r_ids = rmeta.reshape(M * C)
    # second bucketing onto local experts
    C2 = max(1, int(math.ceil(M * C / max(epg, 1) * moe.capacity_factor)))
    oh2 = jax.nn.one_hot(r_ids, epg + 1, dtype=jnp.int32)
    pos2 = jnp.take_along_axis(jnp.cumsum(oh2, 0) - 1, r_ids[:, None], 1)[:, 0]
    ok2 = (r_ids < epg) & (pos2 < C2)
    buf = jnp.zeros((epg, C2, d), x2.dtype).at[
        jnp.where(ok2, r_ids, epg), jnp.where(ok2, pos2, C2)].set(
        rows, mode="drop")
    gate = pe["gate"] if cfg.glu else None
    out_buf = _expert_ffn(buf, gate, pe["up"], pe["down"],
                          cfg.act, cfg.glu, cdt)
    rows_out = out_buf[jnp.clip(r_ids, 0, epg - 1), jnp.clip(pos2, 0, C2 - 1)]
    rows_out = jnp.where(ok2[:, None], rows_out, 0)
    yback = jax.lax.all_to_all(rows_out.reshape(M, C, d), a2a_axis, 0, 0,
                               tiled=True)
    got = yback[dest, jnp.clip(pos, 0, C - 1)]              # [F, d]
    got = jnp.where(valid[:, None], got, 0) * f_w[:, None].astype(got.dtype)
    y_my = jnp.zeros((Tm, d), jnp.float32).at[f_tok].add(got.astype(jnp.float32))
    if moe.n_shared:
        y_my = y_my + _shared_ffn(x_my, p["shared"], cfg, cdt).astype(jnp.float32)
    y = jax.lax.all_gather(y_my.astype(x2.dtype), model_axis, axis=0,
                           tiled=True)                      # [T, d]
    return y, aux


# -------------------------------------------------------------- public


def moe_block(p, x, cfg: ArchConfig, dist, dispatch: str = "auto"):
    """x: [B, S, d] -> (y [B, S, d], aux). Chooses a dispatch strategy."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    if not dist.active or dist.model_size == 1:
        if dist.active:
            x2 = dist.constrain(x2, P(dist.dp_axes, None))
        y, aux = moe_local(p, x2, cfg)
        return y.reshape(B, S, d), aux

    lay = expert_layout(cfg, dist.ep_size)
    tokens_per_dev = (B * S) // max(dist.dp_size, 1)
    if dist.ep_over_dp:
        dispatch = "a2a"
    elif dispatch == "auto":
        dispatch = "a2a" if tokens_per_dev >= 4 * lay.M else "replicated"
    body = _moe_a2a_body if dispatch == "a2a" else _moe_replicated_body

    pspecs = moe_param_specs(cfg, dist)
    xspec = P(dist.dp_axes, None)
    aux_spec = {"lb_loss": P(), "z_loss": P(), "drop_frac": P()}

    def wrapped(p_, x2_):
        y, aux = body(p_, x2_, cfg, lay, dist)
        aux = {k: jax.lax.pmean(jax.lax.pmean(v, dist.model_axis), dist.dp_axes)
               for k, v in aux.items()}
        return y, aux

    y, aux = jax.shard_map(
        wrapped, mesh=dist.mesh,
        in_specs=(pspecs, xspec),
        out_specs=(xspec, aux_spec),
        check_vma=False,
    )(p, x2)
    return y.reshape(B, S, d), aux
