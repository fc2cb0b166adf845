"""Plain reference of the Qwen2 decoder (Qwen1.5): RMSNorm, grouped-query
attention with biases on q, k and v and rotary positions, a SwiGLU MLP,
and the unembedding tied to the embedding. Keys are those of the
published ``config.json``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import bias, causal_attention, rms_norm, rope


def forward(w, tokens, cfg, dense, out_from: int, n_out: int):
    """Logits ``[n_out, vocab]`` at positions ``out_from ..`` of the
    sequence ``tokens [S]``; ``w`` has the served model's layout."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    KV, hd = cfg["num_key_value_heads"], d // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = tokens.shape[0]

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = rms_norm(x, p["norm1"]["scale"], eps)
        q = (dense(h, a["wq"]["w"]) + bias(a["wq"])).reshape(S, H, hd)
        k = (dense(h, a["wk"]["w"]) + bias(a["wk"])).reshape(S, KV, hd)
        v = (dense(h, a["wv"]["w"]) + bias(a["wv"])).reshape(S, KV, hd)
        o = causal_attention(rope(q, theta), rope(k, theta), v)
        x = x + dense(o.reshape(S, H * hd), a["wo"]["w"])
        h = rms_norm(x, p["norm2"]["scale"], eps)
        g = jax.nn.silu(dense(h, m["gate"]["w"])) * dense(h, m["up"]["w"])
        return x + dense(g, m["down"]["w"]), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = rms_norm(jax.lax.dynamic_slice_in_dim(x, out_from, n_out),
                 w["final_norm"]["scale"], eps)
    return dense(x, w["embed"].T)


def counts(cfg) -> work.Decoder:
    return work.Decoder(cfg, glu=True, biases=("q", "k", "v"))
