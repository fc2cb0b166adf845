"""AdamW with global-norm clipping, cosine schedule, and configurable
optimizer-state dtype (bf16 m/v halves the optimizer state, for the
314B/671B configs)."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.layers import DTYPES


@dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    state_dtype: str = "float32"     # "bfloat16" halves optimizer memory


def init_opt_state(params, cfg: OptConfig):
    sd = DTYPES[cfg.state_dtype]
    zeros = lambda p: jnp.zeros(p.shape, dtype=sd)
    return {"m": jax.tree_util.tree_map(zeros, params),
            "v": jax.tree_util.tree_map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def schedule(cfg: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos)


def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def adamw_update(params, grads, state, cfg: OptConfig):
    step = state["step"] + 1
    lr = schedule(cfg, step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    b1, b2 = cfg.b1, cfg.b2
    sd = DTYPES[cfg.state_dtype]
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        mh = m32 / bc1
        vh = v32 / bc2
        step_dir = mh / (jnp.sqrt(vh) + cfg.eps)
        wd = cfg.weight_decay * p.astype(jnp.float32) if p.ndim >= 2 else 0.0
        p_new = p.astype(jnp.float32) - lr * (step_dir + wd)
        return p_new.astype(p.dtype), m32.astype(sd), v32.astype(sd)

    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state["m"])
    flat_v = tdef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, \
        {"grad_norm": gnorm, "lr": lr}
