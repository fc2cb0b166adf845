"""Unified model API: every assigned architecture behind one interface.

``build_model(cfg, dist)`` returns a ``Model`` whose functions cover the
three lowered entry points of the dry-run matrix:

  train_4k      -> ``loss``   (via train.loop.make_train_step)
  prefill_32k   -> ``prefill``
  decode_32k /
  long_500k     -> ``decode_step``  (one new token against a full cache)

``input_specs(shape)`` returns ShapeDtypeStructs (+ PartitionSpecs) for
every input so the dry-run lowers without allocating anything.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.dist.context import DistContext, no_dist
from repro.models import encdec, hybrid, rwkv6, transformer
from repro.models.layers import dt as _dt


@dataclass
class Model:
    cfg: ArchConfig
    dist: DistContext
    family: str
    pure_dp: bool                    # no TP dim: batch shards over model too
    init: Callable
    param_specs: Callable            # () -> pytree of P (unsanitized)
    loss: Callable                   # (params, batch) -> (loss, metrics)
    init_cache: Callable             # (params, batch, B, max_seq) -> cache
    cache_specs: Callable            # () -> pytree of P
    prefill: Callable                # (params, batch, cache) -> (logits, cache)
    decode_step: Callable            # (params, cache, tokens, lengths) -> ...
    input_specs: Callable            # (shape) -> (struct dict, spec dict)

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))


def _token_inputs(cfg, shape: ShapeConfig, dist: DistContext, pure_dp: bool):
    B, S = shape.global_batch, shape.seq_len
    bspec = dist.dp_axes + ((dist.model_axis,) if (pure_dp and dist.model_axis)
                            else ()) if dist.active else ()
    i32 = jnp.int32
    if shape.kind == "train":
        st = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
              "targets": jax.ShapeDtypeStruct((B, S), i32)}
        sp = {"tokens": P(bspec, None), "targets": P(bspec, None)}
    elif shape.kind == "prefill":
        st = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        sp = {"tokens": P(bspec, None)}
    else:  # decode: one new token against a seq_len cache
        dspec = dist.dp_axes if dist.active else ()
        st = {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
              "lengths": jax.ShapeDtypeStruct((B,), i32)}
        sp = {"tokens": P(dspec, None), "lengths": P(dspec)}
    return st, sp


# ------------------------------------------------------------ LM family


def _build_lm(cfg: ArchConfig, dist: DistContext) -> Model:
    def loss(params, batch):
        return transformer.lm_loss(params, batch["tokens"], batch["targets"],
                                   cfg, dist, remat="full")

    def init_cache(params, batch, B, max_seq):
        return transformer.lm_init_cache(cfg, B, max_seq, dist)

    def prefill(params, batch, cache):
        return transformer.lm_prefill(params, batch["tokens"], cfg, cache,
                                      dist)

    def decode_step(params, cache, tokens, lengths):
        return transformer.lm_decode_step(params, cache, tokens, lengths,
                                          cfg, dist)

    def input_specs(shape):
        st, sp = _token_inputs(cfg, shape, dist, False)
        if cfg.family == "vlm":
            # early fusion: image tokens are ids in the same stream (stub);
            # shapes identical to text tokens.
            pass
        return st, sp

    return Model(cfg=cfg, dist=dist, family=cfg.family, pure_dp=False,
                 init=lambda key: transformer.lm_init(key, cfg, dist),
                 param_specs=lambda: transformer.lm_param_specs(cfg, dist),
                 loss=loss,
                 init_cache=init_cache,
                 cache_specs=lambda: transformer.lm_cache_specs(cfg, dist),
                 prefill=prefill, decode_step=decode_step,
                 input_specs=input_specs)


# --------------------------------------------------------------- hybrid


def _fs_specs(abstract, fs):
    """Pure-DP template: FSDP-shard the largest dim of big leaves."""
    def one(a):
        if a.ndim == 0 or a.size < 1 << 16:
            return P()
        dims = list(a.shape)
        # skip leading stack axis for scanned params
        start = 1 if a.ndim >= 2 else 0
        big = max(range(start, a.ndim), key=lambda i: dims[i])
        spec = [None] * a.ndim
        spec[big] = fs
        return P(*spec)
    return jax.tree_util.tree_map(one, abstract)


def _build_hybrid(cfg: ArchConfig, dist: DistContext) -> Model:
    def loss(params, batch):
        logits, _ = hybrid.hybrid_forward(params, batch["tokens"], cfg, dist,
                                          remat="full")
        return _plain_ce(logits, batch["targets"])

    def init_cache(params, batch, B, max_seq):
        return hybrid.hybrid_states(cfg, B, max_seq, dist)

    def cache_specs():
        dp = dist.dp_axes if dist.active else ()
        m = dist.model_axis
        return {
            "mamba": {"conv": P(None, dp, None, None),
                      "h": P(None, dp, None, None, None)},
            "kv": {"k": P(None, dp, m, None, None),
                   "v": P(None, dp, m, None, None)},
        }

    def param_specs():
        fs = dist.dp_axes[0] if (dist.active and dist.fsdp and dist.dp_axes) \
            else None
        abstract = jax.eval_shape(lambda: hybrid.hybrid_init(jax.random.key(0),
                                                             cfg, dist))
        return _fs_specs(abstract, fs)

    return Model(cfg=cfg, dist=dist, family=cfg.family, pure_dp=True,
                 init=lambda key: hybrid.hybrid_init(key, cfg, dist),
                 param_specs=param_specs,
                 loss=loss, init_cache=init_cache, cache_specs=cache_specs,
                 prefill=lambda p, b, c: hybrid.hybrid_prefill(
                     p, b["tokens"], cfg, c, dist),
                 decode_step=lambda p, c, t, l: hybrid.hybrid_decode_step(
                     p, c, t, l, cfg, dist),
                 input_specs=lambda s: _token_inputs(cfg, s, dist, True))


# ------------------------------------------------------------------ ssm


def _build_rwkv(cfg: ArchConfig, dist: DistContext) -> Model:
    def loss(params, batch):
        logits, _ = rwkv6.rwkv6_lm_apply(params, batch["tokens"], cfg,
                                         remat="full")
        return _plain_ce(logits, batch["targets"])

    def init_cache(params, batch, B, max_seq):
        return rwkv6.rwkv6_lm_states(cfg, B)

    def cache_specs():
        dp = dist.dp_axes if dist.active else ()
        return {"tm_x": P(None, dp, None, None),
                "cm_x": P(None, dp, None, None),
                "S": P(None, dp, None, None, None)}

    def param_specs():
        fs = dist.dp_axes[0] if (dist.active and dist.fsdp and dist.dp_axes) \
            else None
        abstract = jax.eval_shape(
            lambda: rwkv6.rwkv6_lm_init(jax.random.key(0), cfg))
        return _fs_specs(abstract, fs)

    def prefill(params, batch, cache):
        logits, st = rwkv6.rwkv6_lm_apply(params, batch["tokens"], cfg, cache)
        return logits[:, -1, :], st

    def decode_step(params, cache, tokens, lengths):
        logits, st = rwkv6.rwkv6_lm_apply(params, tokens, cfg, cache)
        return logits[:, 0, :], st

    return Model(cfg=cfg, dist=dist, family="ssm", pure_dp=True,
                 init=lambda key: rwkv6.rwkv6_lm_init(key, cfg),
                 param_specs=param_specs,
                 loss=loss, init_cache=init_cache, cache_specs=cache_specs,
                 prefill=prefill, decode_step=decode_step,
                 input_specs=lambda s: _token_inputs(cfg, s, dist, True))


# ---------------------------------------------------------------- audio


def _build_encdec(cfg: ArchConfig, dist: DistContext) -> Model:
    e = cfg.enc_dec

    def loss(params, batch):
        return encdec.encdec_loss(params, batch["frames"], batch["tokens"],
                                  batch["targets"], cfg, dist, remat="full")

    def init_cache(params, batch, B, max_seq):
        return encdec.encdec_init_cache(params, batch["frames"], cfg, B,
                                        max_seq, dist)

    def cache_specs():
        dp = dist.dp_axes if dist.active else ()
        m = dist.model_axis
        return {"self": {"k": P(None, dp, m, None, None),
                         "v": P(None, dp, m, None, None)},
                "cross": {"xk": P(None, dp, None, None, None),
                          "xv": P(None, dp, None, None, None)}}

    def param_specs():
        fs = dist.dp_axes[0] if (dist.active and dist.fsdp and dist.dp_axes) \
            else None
        m = dist.model_axis
        abstract = jax.eval_shape(
            lambda: encdec.encdec_init(jax.random.key(0), cfg, dist))

        def one(path_leaf):
            a = path_leaf
            if a.ndim <= 1 or a.size < 1 << 16:
                return P()
            # dense kernels [.., d_in, d_out]: TP on last, FSDP second-last
            spec = [None] * a.ndim
            spec[-1] = m
            spec[-2] = fs
            return P(*spec)

        specs = jax.tree_util.tree_map(one, abstract)
        return specs

    def prefill(params, batch, cache):
        # encoder runs inside init_cache; prefill = teacher-forced decode
        enc_out = encdec.encode(params, batch["frames"], cfg, dist)
        logits = encdec.decode_forward(params, batch["tokens"], enc_out, cfg,
                                       dist)
        return logits[:, -1, :], cache

    def input_specs(shape):
        st, sp = _token_inputs(cfg, shape, dist, False)
        B = shape.global_batch
        bspec = dist.dp_axes if dist.active else ()
        st["frames"] = jax.ShapeDtypeStruct(
            (B, e.n_frames, cfg.d_model), _dt(cfg.compute_dtype))
        sp["frames"] = P(bspec, None, None)
        return st, sp

    return Model(cfg=cfg, dist=dist, family="audio", pure_dp=False,
                 init=lambda key: encdec.encdec_init(key, cfg, dist),
                 param_specs=param_specs,
                 loss=loss, init_cache=init_cache, cache_specs=cache_specs,
                 prefill=prefill,
                 decode_step=lambda p, c, t, l: encdec.encdec_decode_step(
                     p, c, t, l, cfg, dist),
                 input_specs=input_specs)


# ----------------------------------------------------------------- util


def _plain_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce, {"ce": ce}


BUILDERS = {
    "dense": _build_lm, "moe": _build_lm, "vlm": _build_lm,
    "hybrid": _build_hybrid, "ssm": _build_rwkv, "audio": _build_encdec,
}
# the families whose ``decode_step`` also takes a sequence of caches, one
# per group of rows (``attention.row_groups``), such as one per request
PER_ROW_CACHE_FAMILIES = frozenset(
    f for f, b in BUILDERS.items() if b is _build_lm)


def build_model(cfg: ArchConfig, dist: DistContext = no_dist()) -> Model:
    return BUILDERS[cfg.family](cfg, dist)
