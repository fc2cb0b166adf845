"""Decoder-only LM: dense / MoE FFN x GQA / MLA attention, scanned layers.

Covers chameleon-34b, codeqwen1.5-7b, qwen1.5-0.5b, stablelm-12b,
starcoder2-15b, deepseek-v3-671b, grok-1-314b (and the VLM/early-fusion
case, whose frontend is a token stream).

Parameters are stacked along a leading layer axis and consumed with
``jax.lax.scan``; remat policy is applied per layer. An MoE model's
leading dense layers (``first_k_dense``) are a list of per-layer trees,
``params["dense_layers"]``, run ahead of the scan over the stacked MoE
``params["layers"]``; the KV cache stacks all layers, dense ones first.
The cross-entropy is computed in sequence chunks under ``jax.checkpoint`` so full-vocab logits
never materialize ([B,S,V] at 129k vocab would dominate memory).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.layers import (
    apply_norm, dt, init_embedding, init_mlp, init_norm, mlp, unembed,
)
from repro.models.moe import (
    at_layer, moe_block, moe_init, moe_param_specs, split_experts,
)
from repro.dist.context import DistContext, no_dist

REMAT_POLICIES = {
    "none": None,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "full": jax.checkpoint_policies.nothing_saveable,
}


# ------------------------------------------------------------------ init


def _layer_init(key, cfg: ArchConfig, dtype, model_size: int,
                moe: bool = True) -> dict:
    ks = jax.random.split(key, 4)
    if cfg.attention == "mla":
        a = attn.mla_init(ks[0], cfg, dtype)
    else:
        a = attn.gqa_init(ks[0], cfg, dtype)
    p = {"attn": a,
         "norm1": init_norm(cfg.d_model, cfg.norm, dtype),
         "norm2": init_norm(cfg.d_model, cfg.norm, dtype)}
    if cfg.moe is not None and moe:
        p["moe"] = moe_init(ks[1], cfg, dtype, model_size)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.glu, dtype)
    return p


def lm_init(key, cfg: ArchConfig, dist: DistContext = no_dist()) -> dict:
    dtype = dt(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    K = cfg.first_k_dense
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype, dist.ep_size))(
        layer_keys[K:])
    p = {"embed": init_embedding(ks[1], cfg.vocab, cfg.d_model, dtype),
         "layers": layers,
         "final_norm": init_norm(cfg.d_model, cfg.norm, dtype)}
    if K:
        p["dense_layers"] = [_layer_init(k, cfg, dtype, dist.ep_size,
                                         moe=False) for k in layer_keys[:K]]
    if not cfg.tie_embeddings:
        p["unembed"] = init_embedding(ks[2], cfg.vocab, cfg.d_model, dtype)
    return p


# ------------------------------------------------------------- sharding


def _dense_specs(d_in_axis, d_out_axis, bias_axis, has_bias):
    s = {"w": P(d_in_axis, d_out_axis)}
    if has_bias:
        s["b"] = P(bias_axis)
    return s


def lm_param_specs(cfg: ArchConfig, dist: DistContext) -> dict:
    """PartitionSpecs mirroring lm_init. TP over 'model' on head/ff dims,
    FSDP over dp on d_model dims. Leading scan axis never sharded."""
    if not dist.active:
        return jax.tree_util.tree_map(lambda _: P(), lm_init_abstract(cfg, dist))
    m = dist.model_axis
    fs = dist.dp_axes[0] if (dist.fsdp and dist.dp_axes) else None
    L = None  # layer-stack axis

    def stack(spec: P) -> P:
        return P(L, *spec)

    if cfg.attention == "mla":
        a = {"wq_a": stack(P(fs, None)), "q_norm": stack(P(None)),
             "wq_b": stack(P(None, m)),
             "wkv_a": stack(P(fs, None)), "kv_norm": stack(P(None)),
             "wkv_b": stack(P(None, m)),
             "wo": stack(P(m, fs))}
        a = {k: ({"w": v} if k.startswith("w") else {"scale": v})
             for k, v in a.items()}
    else:
        a = {"wq": {"w": stack(P(fs, m))},
             "wk": {"w": stack(P(fs, m))},
             "wv": {"w": stack(P(fs, m))},
             "wo": {"w": stack(P(m, fs))}}
        if cfg.qkv_bias:
            for k in ("wq", "wk", "wv"):
                a[k]["b"] = stack(P(m))
        if cfg.qk_norm:
            a["q_scale"] = stack(P(None))
            a["k_scale"] = stack(P(None))
    specs = {"attn": a,
             "norm1": _norm_spec(cfg, stack),
             "norm2": _norm_spec(cfg, stack)}
    mp = {"up": {"w": stack(P(fs, m))}, "down": {"w": stack(P(m, fs))}}
    if cfg.glu:
        mp["gate"] = {"w": stack(P(fs, m))}
    if cfg.moe is not None:
        ms = moe_param_specs(cfg, dist)
        specs["moe"] = jax.tree_util.tree_map(
            lambda s: P(L, *s), ms, is_leaf=lambda s: isinstance(s, P))
    else:
        specs["mlp"] = mp
    out = {"embed": P(m, fs),
           "layers": specs,
           "final_norm": _norm_spec(cfg, lambda s: s)}
    if not cfg.tie_embeddings:
        out["unembed"] = P(m, fs)
    if cfg.first_k_dense:
        # an unstacked layer: the stacked specs without their layer axis
        dense = {"attn": a, "norm1": specs["norm1"],
                 "norm2": specs["norm2"], "mlp": mp}
        dense = jax.tree_util.tree_map(lambda s: P(*s[1:]), dense,
                                       is_leaf=lambda s: isinstance(s, P))
        out["dense_layers"] = [dense] * cfg.first_k_dense
    return out


def _norm_spec(cfg, stack):
    s = {"scale": stack(P(None))}
    if cfg.norm == "layernorm":
        s["bias"] = stack(P(None))
    return s


def lm_init_abstract(cfg: ArchConfig, dist: DistContext):
    return jax.eval_shape(lambda: lm_init(jax.random.key(0), cfg, dist))


# --------------------------------------------------------------- forward


def _serve_scan(f, carry, layers, xs, dist: DistContext):
    """``lax.scan`` of ``f(carry, (p_l, xs_l))`` over the stacked layers,
    for the serving steps. On one device an MoE layer's expert matrices
    are not sliced out of the stack per layer, which would copy them every
    call: the block reads them in the whole stack (``moe.at_layer``), and
    an expert that no token chose is not read."""
    if dist.active or "moe" not in layers:
        return jax.lax.scan(f, carry, (layers, xs))
    rest, whole = split_experts(layers["moe"])

    def step(c, sl):
        (p_l, xs_l), i = sl
        return f(c, ({**p_l, "moe": at_layer(p_l["moe"], whole, i)}, xs_l))

    n_layers = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.scan(step, carry, (({**layers, "moe": rest}, xs),
                                      jnp.arange(n_layers)))


def _ffn(p, h, cfg: ArchConfig, dist: DistContext, **moe_kw):
    """A layer's MoE block or dense MLP: ``(y, aux)``, aux None if dense."""
    if "moe" in p:
        return moe_block(p["moe"], h, cfg, dist, **moe_kw)
    with jax.named_scope("mlp.dense"):
        return mlp(p["mlp"], h, cfg.act, cfg.glu, dt(cfg.compute_dtype)), None


def _layer_fwd(p, x, positions, cfg: ArchConfig, dist: DistContext):
    sp = dist.model_axis if (dist.active and dist.seq_parallel) else None
    xs = P(dist.dp_axes, sp, None) if dist.active else None
    h = apply_norm(p["norm1"], x, cfg.norm, cfg.norm_eps)
    if cfg.attention == "mla":
        y = attn.mla_forward(p["attn"], h, cfg, positions)
    else:
        y = attn.gqa_forward(p["attn"], h, cfg, positions)
    x = dist.constrain(x + y, xs) if dist.active else x + y
    h = apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
    y, aux = _ffn(p, h, cfg, dist)
    x = dist.constrain(x + y, xs) if dist.active else x + y
    return x, aux


def _zero_aux():
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32),
            "drop_frac": jnp.zeros((), jnp.float32)}


def lm_backbone(params, tokens, cfg: ArchConfig, dist: DistContext,
                remat: str = "none", positions=None):
    """tokens [B,S] -> hidden [B,S,d], aux."""
    B, S = tokens.shape
    cdt = dt(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if dist.active:
        sp = dist.model_axis if dist.seq_parallel else None
        x = dist.constrain(x, P(dist.dp_axes, sp, None))
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)

    def body(carry, p_l):
        x, aux = carry
        x2, aux_l = _layer_fwd(p_l, x, positions, cfg, dist)
        if aux_l is not None:
            aux = {k: aux[k] + aux_l[k] for k in aux}
        return (x2, aux), None

    f = body
    pol = REMAT_POLICIES.get(remat)
    if remat != "none":
        f = jax.checkpoint(body, policy=pol)
    carry = (x, _zero_aux())
    for p_l in params.get("dense_layers", ()):
        carry, _ = f(carry, p_l)
    (x, aux), _ = jax.lax.scan(f, carry, params["layers"])
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return x, aux


def lm_forward(params, tokens, cfg: ArchConfig, dist: DistContext = no_dist(),
               remat: str = "none"):
    """Full logits [B,S,V] fp32 (small shapes / serving prefill tail)."""
    x, aux = lm_backbone(params, tokens, cfg, dist, remat)
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return unembed(x, w, dt(cfg.compute_dtype)), aux


def lm_loss(params, tokens, targets, cfg: ArchConfig,
            dist: DistContext = no_dist(), remat: str = "full",
            loss_chunk: int = 512, lb_coef: float = 0.01,
            z_coef: float = 1e-4):
    """Sequence-chunked CE; logits never materialize at [B,S,V]."""
    B, S = tokens.shape
    x, aux = lm_backbone(params, tokens, cfg, dist, remat)
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    c = min(loss_chunk, S)
    n = S // c
    xs = x.reshape(B, n, c, -1).swapaxes(0, 1)            # [n,B,c,d]
    ts = targets.reshape(B, n, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_ce(x_c, t_c):
        logits = unembed(x_c, w, dt(cfg.compute_dtype))   # [B,c,V] f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    def body(tot, sl):
        x_c, t_c = sl
        return tot + chunk_ce(x_c, t_c), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    ce = tot / (B * S)
    loss = ce
    if cfg.moe is not None:
        loss = loss + lb_coef * aux["lb_loss"] / cfg.n_layers \
            + z_coef * aux["z_loss"] / cfg.n_layers
    metrics = {"ce": ce, **{k: v / cfg.n_layers for k, v in aux.items()}}
    return loss, metrics


# ----------------------------------------------------------------- cache


def lm_init_cache(cfg: ArchConfig, batch: int, max_seq: int,
                  dist: DistContext = no_dist()):
    dtype = dt(cfg.param_dtype)

    def one(_):
        if cfg.attention == "mla":
            return attn.mla_init_cache(cfg, batch, max_seq, dtype)
        return attn.gqa_init_cache(cfg, batch, max_seq, dtype)

    return jax.vmap(one)(jnp.arange(cfg.n_layers))


def lm_cache_specs(cfg: ArchConfig, dist: DistContext):
    """KV cache: batch over dp, sequence over model (flash-decode SP)."""
    if not dist.active:
        dummy = jax.eval_shape(lambda: lm_init_cache(cfg, 1, 8, dist))
        return jax.tree_util.tree_map(lambda _: P(), dummy)
    m = dist.model_axis
    dp = dist.dp_axes
    if cfg.attention == "mla":
        return {"c_kv": P(None, dp, m, None), "k_rope": P(None, dp, m, None)}
    return {"k": P(None, dp, m, None, None), "v": P(None, dp, m, None, None)}


def lm_prefill(params, tokens, cfg: ArchConfig, cache,
               dist: DistContext = no_dist(), remat: str = "none"):
    """Forward + cache fill; returns (last-token logits [B,V], cache)."""
    B, S = tokens.shape
    cdt = dt(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if dist.active:
        x = dist.constrain(x, P(dist.dp_axes, None, None))
    positions = jnp.arange(S)[None, :].repeat(B, 0)

    def body(carry, sl):
        x, = carry
        p_l, cache_l = sl
        h = apply_norm(p_l["norm1"], x, cfg.norm, cfg.norm_eps)
        if cfg.attention == "mla":
            y, cache_l = attn.mla_prefill(p_l["attn"], h, cfg, cache_l, positions)
        else:
            y, cache_l = attn.gqa_prefill(p_l["attn"], h, cfg, cache_l, positions)
        x = x + y
        h = apply_norm(p_l["norm2"], x, cfg.norm, cfg.norm_eps)
        y, _ = _ffn(p_l, h, cfg, dist)
        return (x + y,), cache_l

    f = jax.checkpoint(body, policy=None) if remat != "none" else body
    K = cfg.first_k_dense
    dense = []
    for i, p_l in enumerate(params.get("dense_layers", ())):
        (x,), c = f((x,), (p_l, {k: v[i] for k, v in cache.items()}))
        dense.append(c)
    (x,), new_cache = _serve_scan(f, (x,), params["layers"],
                                  {k: v[K:] for k, v in cache.items()}, dist)
    if dense:
        new_cache = {k: jnp.concatenate([jnp.stack([c[k] for c in dense]),
                                         v]) for k, v in new_cache.items()}
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x[:, -1:, :], w, cdt)
    return logits[:, 0, :], new_cache


def lm_decode_step(params, cache, tokens, lengths, cfg: ArchConfig,
                   dist: DistContext = no_dist()):
    """tokens [B,1], lengths [B] -> (logits [B,V], cache).

    ``cache`` is one cache of batch B, or a sequence of caches whose
    batches stack into the B rows, such as one cache of batch 1 per
    request (``attention.row_groups``); the step returns the same. The
    embedding, projections, FFN and unembedding run once over the B rows,
    so each weight is read once; each row attends over its own cache.

    The layer loop only reads the caches: each layer attends over its
    slice and over the new token, and hands the token's entries out of the
    loop, which writes all layers' at once at ``(:, b, lengths[b])``. A
    caller that donates the caches so has them updated in place, not
    copied."""
    cdt = dt(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    decode = (attn.mla_decode_token if cfg.attention == "mla"
              else attn.gqa_decode_token)
    groups = attn.row_groups(cache, stacked=True)

    def body(x, sl):
        p_l, layer = sl
        h = apply_norm(p_l["norm1"], x, cfg.norm, cfg.norm_eps)
        cache_l = [{k: c[layer] for k, c in g.items()} for _, g in groups]
        y, new = decode(p_l["attn"], h, cfg, cache_l, lengths)
        x = x + y
        h = apply_norm(p_l["norm2"], x, cfg.norm, cfg.norm_eps)
        y, _ = _ffn(p_l, h, cfg, dist,
                    dispatch="replicated" if dist.active else "auto")
        return x + y, new

    K = cfg.first_k_dense
    dense = []
    for i, p_l in enumerate(params.get("dense_layers", ())):
        x, n = body(x, (p_l, i))
        dense.append(n)
    x, new = _serve_scan(body, x, params["layers"],
                         jnp.arange(K, cfg.n_layers), dist)
    if dense:
        new = {k: jnp.concatenate([jnp.stack([n[k] for n in dense]), v])
               for k, v in new.items()}
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, w, cdt)
    written = tuple(attn.write_tokens(g, {k: v[:, r] for k, v in new.items()},
                                      lengths[r], stacked=True)
                    for r, g in groups)
    return logits[:, 0, :], written[0] if isinstance(cache, dict) else written


# ------------------------------------------------- optional: MTP head
# deepseek-v3 trains with a multi-token-prediction module: one extra
# transformer layer predicting token t+2 from [h_t ; emb(t+1)].


def mtp_init(key, cfg: ArchConfig, dist: DistContext = no_dist()) -> dict:
    dtype = dt(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    return {"proj": init_embedding(ks[0], 2 * cfg.d_model, cfg.d_model, dtype),
            "layer": _layer_init(ks[1], cfg, dtype, dist.ep_size)}


def mtp_loss(params, mtp_params, tokens, targets2, cfg: ArchConfig,
             dist: DistContext = no_dist(), remat: str = "none"):
    """targets2 = tokens shifted by 2. Returns CE of the MTP head."""
    B, S = tokens.shape
    cdt = dt(cfg.compute_dtype)
    h, _ = lm_backbone(params, tokens, cfg, dist, remat)
    nxt = jnp.take(params["embed"], jnp.roll(tokens, -1, axis=1), 0).astype(cdt)
    z = jnp.concatenate([h.astype(cdt), nxt], axis=-1)
    x = jnp.einsum("bse,ed->bsd", z, mtp_params["proj"].astype(cdt))
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    x, _ = _layer_fwd(mtp_params["layer"], x, positions, cfg, dist)
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, w, cdt)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets2[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
