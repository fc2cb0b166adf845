"""Plain reference of DeepSeek-V3 as one chip's share of an expert-parallel
deployment. Keys are those of the published ``config.json``, with the
share's own: ``n_routed_experts_held`` experts from
``first_held_expert`` on, of ``n_routed_experts``.

The forward pass follows DeepSeek's published inference code
(``inference/model.py``) with its attention decompressed:

- RMSNorm (``rms_norm_eps``) before attention and before the MLP, and on
  the query and key-value latents;
- latent attention: queries through ``q_lora_rank``, keys and values
  through ``kv_lora_rank``, per head ``qk_nope_head_dim`` +
  ``qk_rope_head_dim`` for scores and ``v_head_dim`` for values, one
  rope key shared by the heads; rotary on interleaved pairs, its
  frequencies YaRN-scaled (``rope_scaling``), and the softmax scale
  multiplied by YaRN's mscale squared; causal, in query blocks, and the
  MLPs in blocks of rows;
- the first ``first_k_dense_replace`` layers with a SwiGLU MLP of
  ``intermediate_size``, the rest with the MoE layer: sigmoid scores,
  a correction ``bias`` added for choosing only, ``n_group`` groups of
  which the ``topk_group`` with the best sum of their top-2 biased
  scores are kept, the ``num_experts_per_tok`` best experts of those,
  weighted by their unbiased scores normalised over all chosen
  (``norm_topk_prob``) and scaled by ``routed_scaling_factor``. Only the
  held experts' terms are added, one expert at a time over every token,
  and the shared experts' (``n_shared_experts``) to every token;
- the untied head over the sliced vocabulary.

The multi-token-prediction module is not served and not here. Every
matrix product, the router's too, goes through the ``dense`` it is given.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import work
from bench.reference import BLOCK, HIGHEST, rms_norm

F32 = jnp.float32
# heads whose attention the reference computes at a time
HEADS = 16


def yarn_inv_freq(cfg) -> jnp.ndarray:
    """The rotary frequencies of the rope dims, YaRN-scaled."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    freqs = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=F32) / dim))

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    smooth = 1 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                          / (high - low), 0, 1)
    return freqs / rs["factor"] * (1 - smooth) + freqs * smooth


def softmax_scale(cfg) -> float:
    rs = cfg["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return qk ** -0.5 * mscale * mscale


def rope_interleaved(x, inv_freq, positions):
    """Rotary of ``x [n, heads, rope]`` at ``positions [n]``, on the pairs
    (0, 1), (2, 3), ... as complex numbers."""
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xr[..., 0], xr[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def by_rows(f, x, most: int = 8):
    """``f(rows, i)`` over the ``i``-th blocks of rows of ``x [S, ...]`` (a
    multiple of ``BLOCK`` rows), up to ``most`` BLOCKs at a time. Only one
    block's activations are alive at a time, so that the reference of a
    long sequence fits beside the weights."""
    nb = x.shape[0] // BLOCK
    g = max(i for i in range(1, most + 1) if nb % i == 0)  # BLOCKs a step
    out = jax.lax.map(lambda a: f(*a), (
        x.reshape(nb // g, g * BLOCK, *x.shape[1:]), jnp.arange(nb // g)))
    return out.reshape(x.shape[0], *out.shape[2:])


def mla(x, a, cfg, dense, inv_freq):
    """Decompressed latent attention, causal, ``HEADS`` heads at a time:
    their queries, keys and values at every position, then their scores a
    block of query rows at a time. A score is the no-rope part's product
    plus the rope part's, the rope key shared by the heads."""
    S = x.shape[0]
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lora, vd, ql = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg["q_lora_rank"]
    G = min(HEADS, H)
    pos = jnp.arange(S)
    kv = dense(x, a["wkv_a"]["w"])
    k_pe = rope_interleaved(kv[:, None, lora:], inv_freq, pos)[:, 0]
    c = rms_norm(kv[:, :lora], a["kv_norm"]["scale"], eps)
    qa = rms_norm(dense(x, a["wq_a"]["w"]), a["q_norm"]["scale"], eps)
    w_q = a["wq_b"]["w"].reshape(ql, H, nope + rope)
    w_kv = a["wkv_b"]["w"].reshape(lora, H, nope + vd)
    scale = softmax_scale(cfg)

    def heads(_, g):
        def cols(w, lo, hi):        # these heads' columns of w
            w = jax.lax.dynamic_slice_in_dim(w, g * G, G, axis=1)
            return w[..., lo:hi].reshape(w.shape[0], -1)

        q = dense(qa, cols(w_q, 0, nope + rope)).reshape(S, G, nope + rope)
        q_pe = rope_interleaved(q[..., nope:], inv_freq, pos)
        k = dense(c, cols(w_kv, 0, nope)).reshape(S, G, nope)
        v = dense(c, cols(w_kv, nope, nope + vd)).reshape(S, G, vd)

        def block(qi, i):
            qn, qp = qi[..., :nope], qi[..., nope:]
            qpos = i * BLOCK + jnp.arange(BLOCK)
            s = (jnp.einsum("qhd,thd->hqt", qn, k, precision=HIGHEST)
                 + jnp.einsum("qhd,td->hqt", qp, k_pe, precision=HIGHEST)) \
                * scale
            s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
            return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(s, -1), v,
                              precision=HIGHEST)

        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        return None, by_rows(block, q, most=1)

    _, o = jax.lax.scan(heads, None, jnp.arange(H // G))  # [H/G, S, G, vd]
    return dense(o.transpose(1, 0, 2, 3).reshape(S, H * vd), a["wo"]["w"])


def swiglu(x, gate, up, down, dense):
    return dense(jax.nn.silu(dense(x, gate)) * dense(x, up), down)


def route(x, m, cfg, dense):
    """(weights [S, k], expert ids [S, k]) of the published router."""
    S = x.shape[0]
    E, G = cfg["n_routed_experts"], cfg["n_group"]
    scores = jax.nn.sigmoid(dense(x, m["router"]))
    choice = (scores + m["bias"].astype(F32)).reshape(S, G, E // G)
    group_scores = jax.lax.top_k(choice, 2)[0].sum(-1)
    groups = jax.lax.top_k(group_scores, cfg["topk_group"])[1]
    kept = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], groups].set(True)
    choice = jnp.where(kept[:, :, None], choice, -jnp.inf).reshape(S, E)
    ids = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, ids, 1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * cfg["routed_scaling_factor"], ids


def moe(x, m, cfg, dense):
    """The held experts' terms and the shared experts'. Expert ``e``'s
    matrices are columns ``e * width ..`` of ``gate`` and ``up``
    ``[hidden, held * moe_intermediate_size]`` and of ``down``
    ``[moe_intermediate_size, held * hidden]``."""
    w, ids = route(x, m, cfg, dense)
    y = swiglu(x, m["shared"]["gate"], m["shared"]["up"],
               m["shared"]["down"], dense)
    f, d = cfg["moe_intermediate_size"], x.shape[1]
    for e in range(cfg["n_routed_experts_held"]):
        we = jnp.where(ids == cfg["first_held_expert"] + e, w, 0.0).sum(-1)
        y = y + we[:, None] * swiglu(x, m["gate"][:, e * f:(e + 1) * f],
                                     m["up"][:, e * f:(e + 1) * f],
                                     m["down"][:, e * d:(e + 1) * d], dense)
    return y


def forward(w, tokens, cfg, dense, out_from: int, n_out: int):
    """Logits ``[n_out, vocab]`` at positions ``out_from ..`` of the
    sequence ``tokens [S]``; ``w`` has the served model's layout: the
    dense layers a list (``dense_layers``), the MoE layers stacked."""
    eps = cfg["rms_norm_eps"]
    inv_freq = yarn_inv_freq(cfg)

    def block(x, p, ffn):
        x = x + mla(rms_norm(x, p["norm1"]["scale"], eps), p["attn"], cfg,
                    dense, inv_freq)
        return x + ffn(rms_norm(x, p["norm2"]["scale"], eps))

    x = w["embed"][tokens].astype(F32)
    for p in w["dense_layers"]:
        m = p["mlp"]
        x = block(x, p, lambda h: by_rows(
            lambda r, _: swiglu(r, m["gate"]["w"], m["up"]["w"],
                                m["down"]["w"], dense), h))
    x, _ = jax.lax.scan(
        lambda x, p: (block(x, p, lambda h: by_rows(
            lambda r, _: moe(r, p["moe"], cfg, dense), h)), None),
        x, w["layers"])
    x = rms_norm(jax.lax.dynamic_slice_in_dim(x, out_from, n_out),
                 w["final_norm"]["scale"], eps)
    return dense(x, w["unembed"].T)


class Counts:
    """Operations and bytes a step requires (``bench/work.py``'s rules),
    for this share of the model: every layer's matrices, and of the
    routed experts only the held ones' expected share of the work,
    ``top_k * held / E`` experts per token and MoE layer. A call reads
    each held expert that its ``n`` tokens are expected to reach,
    ``held * (1 - (1 - top_k / E) ** n)`` of them per MoE layer. Prefill
    attention is decompressed (scores and values per head); decode
    attention is absorbed into the latent (``kv_lora_rank`` and the rope
    dims for scores, ``kv_lora_rank`` for the readout)."""

    def __init__(self, cfg: dict):
        d, H = cfg["hidden_size"], cfg["num_attention_heads"]
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        vd, lora = cfg["v_head_dim"], cfg["kv_lora_rank"]
        ql, V = cfg["q_lora_rank"], cfg["vocab_size"]
        L, K = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
        E, top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
        b = work.DTYPE_BYTES[cfg["serve_dtype"]]
        self.d, self.V, Lm = d, V, L - K
        self.held, self.reach = cfg["n_routed_experts_held"], top / E
        attn = d * ql + ql * H * (nope + rope) + d * (lora + rope) \
            + lora * H * (nope + vd) + H * vd * d
        expert = 3 * d * cfg["moe_intermediate_size"]
        # weight matrices every token passes through, and the routed
        # experts' expected share
        self.fixed_macs = K * (attn + 3 * d * cfg["intermediate_size"]) \
            + Lm * (attn + d * E + cfg["n_shared_experts"] * expert)
        self.routed_macs = Lm * top * self.held / E * expert
        small = L * (2 * d + ql + lora) + Lm * E + d     # norms, biases
        self.fixed_bytes = b * (self.fixed_macs + small + V * d)
        self.expert_bytes = b * Lm * expert              # one held expert
        self.cache_bytes_per_token = b * L * (lora + rope)
        self.embed_row_bytes = b * d
        self.flops_per_pair = 2 * L * H * (nope + rope + vd)
        self.flops_per_position = 2 * L * H * (lora + rope + lora)

    def _weight_bytes(self, n: int) -> float:
        reached = self.held * (1 - (1 - self.reach) ** n)
        return self.fixed_bytes + reached * self.expert_bytes

    def _token_flops(self) -> float:
        return 2 * (self.fixed_macs + self.routed_macs)

    def prefill(self, P: int) -> tuple:
        """(operations, bytes) of one prefill of ``P`` tokens."""
        flops = self._token_flops() * P \
            + self.flops_per_pair * P * (P + 1) // 2 + 2 * self.d * self.V
        nbytes = self._weight_bytes(P) + P * (self.cache_bytes_per_token
                                              + self.embed_row_bytes)
        return flops, nbytes

    def decode(self, lens) -> tuple:
        """(operations, bytes) of one decode call that advances one
        sequence per entry of ``lens``, each attending over that many
        positions, the new one included."""
        flops = sum(self._token_flops() + self.flops_per_position * n
                    + 2 * self.d * self.V for n in lens)
        nbytes = self._weight_bytes(len(lens)) + sum(
            n * self.cache_bytes_per_token + self.embed_row_bytes
            for n in lens)
        return flops, nbytes


def counts(cfg) -> Counts:
    return Counts(cfg)
