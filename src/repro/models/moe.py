"""Mixture-of-Experts FFN with expert parallelism.

Three dispatch strategies:

  * ``held``       — no mesh: the experts this device holds (all of them,
                     or the block ``MoEConfig.n_held`` names) compute
                     every (token, held expert) pair, dropless, as a
                     grouped matmul over the pairs sorted by expert. The
                     router scores all experts; pairs routed to experts
                     held elsewhere add nothing here. Oracle for the
                     sharded paths.
  * ``a2a``        — shard_map expert parallelism: tokens split over the
                     model axis, bucketed per destination expert shard,
                     exchanged with ``lax.all_to_all``, expert-batched
                     matmuls, reverse a2a, weighted combine at the source,
                     all_gather to re-replicate. Used for train/prefill
                     (many tokens per device).
  * ``replicated`` — every model shard routes the full local token set and
                     computes only its own experts; partial outputs are
                     psum'd. No a2a; right for tiny decode batches.

The sharded paths drop tokens beyond their capacity buckets
(``capacity_factor``); with room enough they equal ``held``.

Expert-count < model-axis handling (grok: 8 experts on 16 shards): the
expert hidden dim is split tp_e = M/E ways and each token is dispatched to
all tp_e shards of its expert group; the partial FFN outputs simply add in
the source-side combine (no extra collective). Expert matrices put their
input width first and the device-major experts side by side in the
second: ``[d, M*Epg*ffl]`` for gate and up, ``[ffl, M*Epg*d]`` for down,
expert slot ``m*Epg + j`` (device m's j-th expert, or its share of an
expert's hidden dim) in columns ``slot*ffl ..`` resp. ``slot*d ..`` — see
``expert_layout``. Without a mesh that is ``[d, E_held*d_ff]``. An
expert is then a tile-aligned block of columns, which a device reads
without reading its neighbours.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

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
    if cfg.moe.n_held and M > 1:
        raise ValueError("a held block of experts is one device's share; "
                         "it is not laid out over a mesh")
    ep = math.gcd(E, M)
    tp_e = M // ep
    if E % ep or M % ep:
        raise ValueError(f"cannot lay out {E} experts on model axis {M}")
    ffe = cfg.moe.d_ff or cfg.d_ff
    if ffe % tp_e:
        raise ValueError(f"expert d_ff {ffe} not divisible by tp_e {tp_e}")
    epg = cfg.moe.n_held or E // ep
    return ExpertLayout(M=M, ep=ep, tp_e=tp_e, epg=epg, ffl=ffe // tp_e)


def moe_init(key, cfg: ArchConfig, dtype, model_size: int) -> dict:
    """Expert weights ``[d, M*Epg*ffl]`` / ``[ffl, M*Epg*d]``; the router
    over all experts, and with sigmoid scoring its correction ``bias``."""
    lay = expert_layout(cfg, model_size)
    d = cfg.d_model
    E = cfg.moe.n_experts
    slots = lay.M * lay.epg
    ks = jax.random.split(key, 6)
    std = 1.0 / math.sqrt(d)
    std_ff = 1.0 / math.sqrt(lay.ffl * lay.tp_e)

    def w(k, shape, s):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * s).astype(dtype)

    p = {
        "router": w(ks[0], (d, E), std),
        "up": w(ks[1], (d, slots * lay.ffl), std),
        "down": w(ks[2], (lay.ffl, slots * d), std_ff),
    }
    if cfg.moe.scoring == "sigmoid":
        p["bias"] = jnp.zeros((E,), dtype)
    if cfg.glu:
        p["gate"] = w(ks[3], (d, slots * lay.ffl), std)
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
    # down's d lies inside its expert columns: split them over ep, then fs
    down = (*(ep or ()), *(fs or ()))
    specs = {
        "router": P(None, None),
        "up": P(fs, ep),
        "down": P(None, down or None),
    }
    if cfg.moe.scoring == "sigmoid":
        specs["bias"] = P(None)
    if cfg.glu:
        specs["gate"] = P(fs, ep)
    if cfg.moe.n_shared:
        specs["shared"] = {"up": P(None, None), "down": P(None, None)}
        if cfg.glu:
            specs["shared"]["gate"] = P(None, None)
    return specs


def _gather_experts(p, dist):
    """Inside shard_map: reconstruct this device's whole ``[d, Epg*ffl]``
    / ``[ffl, Epg*d]`` expert blocks by all-gathering what FSDP split over
    the dp axes. With ep_over_dp the weights are already fully local (no
    FSDP dim)."""
    if dist.ep_over_dp or not (dist.fsdp and dist.dp_axes):
        return p
    ax = dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]
    out = dict(p)
    out["up"] = jax.lax.all_gather(p["up"], ax, axis=0, tiled=True)
    if "gate" in p:
        out["gate"] = jax.lax.all_gather(p["gate"], ax, axis=0, tiled=True)
    out["down"] = jax.lax.all_gather(p["down"], ax, axis=1, tiled=True)
    return out


# ------------------------------------------------------------ primitives


def _route(x, p, cfg: ArchConfig):
    """Returns (weights [T,k] f32, ids [T,k] i32, aux dict), choosing among
    all ``n_experts`` as ``MoEConfig`` describes."""
    moe = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32))
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / scores.sum(-1, keepdims=True)
        ids = _group_limited_top_k(scores + p["bias"].astype(jnp.float32),
                                   moe)
        w = jnp.take_along_axis(scores, ids, -1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, moe.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    if moe.routed_scaling != 1.0:
        w = w * moe.routed_scaling
    # load-balance loss (Switch-style) + router z-loss, local means
    me = probs.mean(0)                                     # [E]
    ce = jnp.zeros((moe.n_experts,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / (ids.size))                                  # fraction routed
    lb = moe.n_experts * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return w, ids, {"lb_loss": lb, "z_loss": z}


def _group_limited_top_k(s, moe):
    """Top-k expert ids of the choice scores ``s [T, E]``, taken from the
    ``topk_group`` groups whose two best scores sum highest."""
    T, E = s.shape
    if moe.n_group > 1:
        g = s.reshape(T, moe.n_group, E // moe.n_group)
        group_score = jax.lax.top_k(g, 2)[0].sum(-1)            # [T, G]
        keep = jax.lax.top_k(group_score, moe.topk_group)[1]
        kept = jax.nn.one_hot(keep, moe.n_group, dtype=jnp.int32).sum(1)
        s = jnp.where(kept[:, :, None] > 0, g, -jnp.inf).reshape(T, E)
    return jax.lax.top_k(s, moe.top_k)[1]


def _expert_ffn(hbuf, p_gate, p_up, p_down, act: str, glu: bool, cdt):
    """hbuf [E?, C, d] x per-expert weights [d, E?*ffl] / [ffl, E?*d]
    -> [E?, C, d]."""
    E, _, d = hbuf.shape

    def w(m, per):      # [d_in, E*per] -> [d_in, E, per]
        return m.reshape(m.shape[0], E, per).astype(cdt)

    ffl = p_up.shape[1] // E
    h = jnp.einsum("ecd,def->ecf", hbuf.astype(cdt), w(p_up, ffl))
    if glu:
        g = jnp.einsum("ecd,def->ecf", hbuf.astype(cdt), w(p_gate, ffl))
        h = ACTS[act](g) * h
    else:
        h = ACTS[act](h)
    return jnp.einsum("ecf,fed->ecd", h, w(p_down, d))


def _ffn(x, p, cfg, cdt):
    """One expert (``p``: ``up``, ``down``, ``gate`` matrices) on ``x``."""
    h = jnp.dot(x.astype(cdt), p["up"].astype(cdt))
    if cfg.glu:
        h = ACTS[cfg.act](jnp.dot(x.astype(cdt), p["gate"].astype(cdt))) * h
    else:
        h = ACTS[cfg.act](h)
    return jnp.dot(h, p["down"].astype(cdt))


# -------------------------------------------------------- held experts

EXPERT_MATRICES = ("gate", "up", "down")


class Stacked(NamedTuple):
    """A layer's expert matrix inside the whole stack of layers: ``a`` is
    ``[L, ...]`` and the layer is ``a[i]``. The held-expert layer slices
    one expert out of it only where that expert has rows, so that a layer
    loop need not slice (and copy) every layer's experts whole."""
    a: jax.Array
    i: jax.Array


def split_experts(p: dict) -> tuple[dict, dict]:
    """A stacked MoE block's parameters as ``(rest, whole)``: ``whole``
    holds the expert matrices, left in the stack of layers for
    ``at_layer`` to index; ``rest`` is sliced per layer as usual."""
    whole = {n: w for n, w in p.items() if n in EXPERT_MATRICES}
    return {n: w for n, w in p.items() if n not in whole}, whole


def at_layer(rest_l: dict, whole: dict, i) -> dict:
    """Layer ``i``'s MoE block parameters: its slice ``rest_l`` of
    ``split_experts``' ``rest``, and its expert matrices as ``Stacked``
    views of ``whole``."""
    return {**rest_l, **{n: Stacked(w, i) for n, w in whole.items()}}


def _expert(w, e, width: int):
    """Expert ``e``'s ``[d_in, width]`` block of ``w``, ``[d_in,
    E*width]`` or a ``Stacked`` of such."""
    if isinstance(w, Stacked):
        d_in = w.a.shape[1]
        return jax.lax.dynamic_slice(w.a, (w.i, 0, e * width),
                                     (1, d_in, width))[0]
    return jax.lax.dynamic_slice_in_dim(w, e * width, width, axis=1)


# rows of a tile of the grouped matmul: large enough to keep the MXU busy
# at prefill, where an expert held here sees a few hundred tokens
TILE_ROWS = 256


def _held_experts(p, x2, w, ids, cfg: ArchConfig, cdt):
    """This device's experts' part of the layer, dropless: every (token,
    held expert) pair, weighted by its routing weight, summed per token.
    The pairs are sorted by expert and each expert runs over its own rows
    in tiles; a tile with no row of its expert is skipped, so an expert
    that no token chose is not read."""
    moe = cfg.moe
    T, d = x2.shape
    k = ids.shape[1]
    lay = expert_layout(cfg, 1)
    E, width = lay.epg, {"gate": lay.ffl, "up": lay.ffl, "down": d}
    R = min(TILE_ROWS, T)            # an expert has at most T rows
    n_tiles = -(-T // R)
    local = ids.reshape(-1) - moe.held_first
    g = jnp.where((local >= 0) & (local < E), local, E)    # E: held elsewhere
    order = jnp.argsort(g, stable=True)
    sizes = jnp.sum(g[:, None] == jnp.arange(E)[None, :], axis=0)
    starts = jnp.cumsum(sizes) - sizes
    tok = jnp.concatenate([order // k, jnp.zeros((R,), order.dtype)])
    wt = jnp.concatenate([w.reshape(-1)[order], jnp.zeros((R,), w.dtype)])
    names = [n for n in EXPERT_MATRICES if n in p]

    def tile(y, j):
        e, i = j // n_tiles, j % n_tiles

        def run(y):
            at = starts[e] + i * R
            rows = jax.lax.dynamic_slice_in_dim(tok, at, R)
            rw = jnp.where(jnp.arange(R) < sizes[e] - i * R,
                           jax.lax.dynamic_slice_in_dim(wt, at, R), 0.0)
            ex = {n: _expert(p[n], e, width[n]) for n in names}
            out = _ffn(x2[rows], ex, cfg, cdt).astype(jnp.float32)
            return y.at[rows].add(out * rw[:, None])

        return jax.lax.cond(i * R < sizes[e], run, lambda y: y, y), None

    y, _ = jax.lax.scan(tile, jnp.zeros((T, d), jnp.float32),
                        jnp.arange(E * n_tiles))
    return y


def moe_held(p, x2, cfg: ArchConfig):
    """The layer on one device without a mesh: route over all experts,
    compute the held experts' pairs (``_held_experts``) and the shared
    expert."""
    cdt = dt(cfg.compute_dtype)
    with jax.named_scope("moe.route"):
        w, ids, aux = _route(x2, p, cfg)
    with jax.named_scope("moe.experts"):
        y = _held_experts(p, x2, w, ids, cfg, cdt)
    aux["drop_frac"] = jnp.zeros((), jnp.float32)
    if cfg.moe.n_shared:
        with jax.named_scope("moe.shared"):
            y = y + _ffn(x2, p["shared"], cfg, cdt).astype(jnp.float32)
    return y.astype(x2.dtype), aux


# --------------------------------------------------- sharded: replicated


def _moe_replicated_body(p, x2, cfg: ArchConfig, lay: ExpertLayout, dist):
    """Every model shard holds all local tokens; computes own experts; psum."""
    model_axis = dist.model_axis
    moe = cfg.moe
    cdt = dt(cfg.compute_dtype)
    T, d = x2.shape
    pe = _gather_experts(p, dist)
    w, ids, aux = _route(x2, p, cfg)
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
        y = y + _ffn(x2, p["shared"], cfg, cdt).astype(jnp.float32)
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
    w, ids, aux = _route(x_my, p, cfg)

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
        y_my = y_my + _ffn(x_my, p["shared"], cfg, cdt).astype(jnp.float32)
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
        y, aux = moe_held(p, x2, cfg)
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
