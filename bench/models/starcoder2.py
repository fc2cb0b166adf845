"""Plain reference of the StarCoder2 decoder: LayerNorm, grouped-query
attention with rotary positions, a plain MLP with the tanh GELU
(``gelu_pytorch_tanh``), biases on every projection (``use_bias``), and
an untied unembedding. Keys are those of the published ``config.json``.

Where the served model's layout has no place for a bias (the output
projection and both MLP projections), the benchmark's weights hold no
such bias and the reference adds 0: the same function as the published
architecture with those biases at zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import bias, causal_attention, layer_norm, rope


def forward(w, tokens, cfg, dense, out_from: int, n_out: int):
    """Logits ``[n_out, vocab]`` at positions ``out_from ..`` of the
    sequence ``tokens [S]``; ``w`` has the served model's layout."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    KV, hd = cfg["num_key_value_heads"], d // H
    eps, theta = cfg["norm_epsilon"], cfg["rope_theta"]
    S = tokens.shape[0]

    def norm(p, x):
        return layer_norm(x, p["scale"], p["bias"], eps)

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = norm(p["norm1"], x)
        q = (dense(h, a["wq"]["w"]) + bias(a["wq"])).reshape(S, H, hd)
        k = (dense(h, a["wk"]["w"]) + bias(a["wk"])).reshape(S, KV, hd)
        v = (dense(h, a["wv"]["w"]) + bias(a["wv"])).reshape(S, KV, hd)
        o = causal_attention(rope(q, theta), rope(k, theta), v)
        x = x + dense(o.reshape(S, H * hd), a["wo"]["w"]) + bias(a["wo"])
        h = norm(p["norm2"], x)
        h = jax.nn.gelu(dense(h, m["up"]["w"]) + bias(m["up"]),
                        approximate=True)
        return x + dense(h, m["down"]["w"]) + bias(m["down"]), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = norm(w["final_norm"], jax.lax.dynamic_slice_in_dim(x, out_from, n_out))
    return dense(x, w["unembed"].T)


def counts(cfg) -> work.Decoder:
    return work.Decoder(cfg, glu=False, norm_params=2,
                        biases=("q", "k", "v", "o", "up", "down"))
