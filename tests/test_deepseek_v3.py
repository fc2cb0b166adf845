"""DeepSeek-V3 as one chip's share of an expert-parallel deployment: the
served path against the benchmark's plain reference at a small size, the
held-expert layer (shares add up, dropless), the published router, the
configuration file against the registry, and the weights the benchmark
draws for the new leaves."""
import dataclasses
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import REPO

sys.path.insert(0, str(REPO))

from bench import harness, reference, weights  # noqa: E402
from bench.models import deepseek_v3 as ref  # noqa: E402
from repro.configs import YarnConfig, get_arch  # noqa: E402
from repro.dist.context import no_dist  # noqa: E402
from repro.launch.serve import serve_steps  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.api import build_model  # noqa: E402

CONFIG = json.loads((REPO / "bench" / "configs" / "deepseek-v3-ep32.json")
                    .read_text())
# a small share: 1 dense + 2 MoE layers, 2 of 8 experts held from expert 2,
# and YaRN over 64 positions so that the prompt runs past them
SMALL = {**CONFIG, "hidden_size": 64, "intermediate_size": 96,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "q_lora_rank": 32, "kv_lora_rank": 16, "qk_rope_head_dim": 16,
         "qk_nope_head_dim": 16, "v_head_dim": 16,
         "moe_intermediate_size": 32, "n_routed_experts": 8, "n_group": 4,
         "topk_group": 2, "num_experts_per_tok": 2,
         "n_routed_experts_held": 2, "first_held_expert": 2,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "vocab_size": 512, "serve_dtype": "float32",
         "rope_scaling": {**CONFIG["rope_scaling"],
                          "original_max_position_embeddings": 64}}


def small_arch(cfg: dict = SMALL):
    """The program's configuration of ``cfg``: the registry's share with
    the small widths that ``harness.program_arch`` does not carry."""
    arch = get_arch(cfg["registry"])
    rs = cfg["rope_scaling"]
    arch = dataclasses.replace(
        arch, first_k_dense=cfg["first_k_dense_replace"],
        rope_scaling=YarnConfig(
            factor=rs["factor"],
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        mla=dataclasses.replace(
            arch.mla, q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            rope_head_dim=cfg["qk_rope_head_dim"],
            nope_head_dim=cfg["qk_nope_head_dim"],
            v_head_dim=cfg["v_head_dim"]),
        moe=dataclasses.replace(
            arch.moe, n_experts=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            d_ff=cfg["moe_intermediate_size"],
            n_held=cfg["n_routed_experts_held"],
            held_first=cfg["first_held_expert"]))
    return dataclasses.replace(
        arch, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["serve_dtype"], compute_dtype=cfg["serve_dtype"])


def test_yarn_matches_the_published_check_values():
    from repro.models.layers import rope_freqs, yarn_mscale, yarn_range
    yarn = get_arch("deepseek-v3-671b").rope_scaling
    assert yarn_range(64, 10000.0, yarn) == (10, 23)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    m = 1 - np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(np.asarray(rope_freqs(64, 10000.0, yarn)),
                               plain / 40 * (1 - m) + plain * m, rtol=1e-6)
    assert 192 ** -0.5 * yarn_mscale(yarn) ** 2 == pytest.approx(0.1352,
                                                                 abs=1e-4)


def test_served_logits_match_the_reference(monkeypatch):
    """Prefill, then decode through the cache, give the reference's
    logits at every position, past YaRN's original context too."""
    monkeypatch.setattr(ref, "HEADS", 2)    # two steps over the heads
    arch = small_arch()
    model = build_model(arch, no_dist())
    w = weights.make_weights(model, 2**35 + 17, jax.devices()[0])
    P, steps = 72, 6
    prefill, decode = serve_steps(model, P + steps)
    toks = np.random.default_rng(0).integers(0, SMALL["vocab_size"], (1, P),
                                             np.int32)
    cache = model.init_cache(w, {"tokens": toks}, 1, P + steps)
    served = [model.prefill(w, {"tokens": toks}, cache)[0]]
    tok, _, cache, lengths = prefill(w, toks)
    seq = list(toks[0])
    for _ in range(steps):
        seq.append(int(tok[0, 0]))
        served.append(model.decode_step(w, cache, tok, lengths)[0])
        tok, _, cache, lengths = decode(w, cache, tok, lengths)
    got = np.concatenate([np.asarray(s) for s in served])
    S = reference.BLOCK
    want = ref.forward(w, jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32),
                       SMALL, reference.dense_f32, P - 1, steps + 1)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)


def _layer(n_held, first):
    """A small MoE layer holding ``n_held`` experts from ``first`` (0: all
    8), with a random correction bias."""
    arch = small_arch()
    arch = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, n_held=n_held, held_first=first))
    p = moe.moe_init(jax.random.key(0), arch, jnp.float32, 1)
    p["bias"] = jax.random.uniform(jax.random.key(1), p["bias"].shape,
                                   minval=-0.35, maxval=0.35)
    return arch, p


def test_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts each: their results, with the shared
    expert counted once, are the uncut reference layer's."""
    arch, p = _layer(0, 0)
    x = jax.random.normal(jax.random.key(5), (40, SMALL["hidden_size"]))
    shared = moe._ffn(x, p["shared"], arch, jnp.float32)
    total = shared
    for first in range(0, 8, 2):
        share = dataclasses.replace(arch, moe=dataclasses.replace(
            arch.moe, n_held=2, held_first=first))
        ps = {**p, **{n: p[n][:, first * w:(first + 2) * w] for n, w in
                      (("gate", 32), ("up", 32), ("down", 64))}}
        total = total + moe.moe_held(ps, x, share)[0] - shared
    whole = ref.moe(x, p, {**SMALL, "n_routed_experts_held": 8,
                           "first_held_expert": 0}, reference.dense_f32)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(moe.moe_held(p, x, arch)[0]),
                               np.asarray(whole), rtol=2e-5, atol=2e-5)


def test_every_pair_is_computed_when_all_tokens_pick_the_same_experts():
    """Dropless: 300 tokens all routed to the two held experts, more rows
    than one tile of the grouped matmul, are each computed."""
    arch, p = _layer(2, 2)
    p["router"] = jnp.zeros_like(p["router"])
    p["bias"] = jnp.zeros(8).at[2].set(1.0).at[3].set(0.9)
    T = moe.TILE_ROWS + 44
    x = jax.random.normal(jax.random.key(6), (T, SMALL["hidden_size"]))
    got = moe.moe_held(p, x, arch)[0] - moe._ffn(x, p["shared"], arch,
                                                  jnp.float32)
    # each expert's weight: sigmoid(0) normalised over the two, times 2.5
    want = sum(1.25 * moe._ffn(x, {n: p[n][:, e * w:(e + 1) * w] for n, w in
                                   (("gate", 32), ("up", 32), ("down", 64))},
                               arch, jnp.float32) for e in (0, 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def published_gate(x, weight, bias, n_groups, topk_groups, topk,
                   route_scale):
    """DeepSeek-V3's ``Gate.forward`` (inference/model.py), transcribed to
    numpy for sigmoid scoring with a correction bias."""
    scores = 1 / (1 + np.exp(-(x @ weight.T)))
    original_scores = scores
    scores = scores + bias
    scores = scores.reshape(x.shape[0], n_groups, -1)
    group_scores = np.sort(scores, -1)[..., -2:].sum(-1)
    indices = np.argsort(-group_scores, -1)[:, :topk_groups]
    mask = np.ones((x.shape[0], n_groups), bool)
    np.put_along_axis(mask, indices, False, 1)
    scores = np.where(mask[..., None], -np.inf, scores).reshape(x.shape[0], -1)
    indices = np.argsort(-scores, -1)[:, :topk]
    weights = np.take_along_axis(original_scores, indices, 1)
    weights = weights / weights.sum(-1, keepdims=True)
    return weights * route_scale, indices


def test_router_is_the_published_group_limited_selection():
    cfg = get_arch("deepseek-v3-671b")
    d, E = 96, cfg.moe.n_experts
    rng = np.random.default_rng(3)
    x = rng.normal(size=(64, d)).astype(np.float32)
    router = (rng.normal(size=(d, E)) / math.sqrt(d)).astype(np.float32)
    bias = rng.uniform(-0.35, 0.35, E).astype(np.float32)
    w, ids, _ = moe._route(jnp.asarray(x), {"router": router, "bias": bias},
                           dataclasses.replace(cfg, d_model=d))
    want_w, want_ids = published_gate(x, router.T, bias, 8, 4, 8, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1),
                                  np.sort(want_ids, 1))
    order = np.argsort(np.asarray(ids), 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1),
        np.take_along_axis(want_w, np.argsort(want_ids, 1), 1), rtol=1e-5)


def test_configuration_file_is_the_registry_share():
    arch = harness.program_arch(CONFIG)
    m, moe_cfg = arch.mla, arch.moe
    rs = CONFIG["rope_scaling"]
    assert (m.q_lora_rank, m.kv_lora_rank, m.rope_head_dim, m.nope_head_dim,
            m.v_head_dim) == tuple(CONFIG[k] for k in (
                "q_lora_rank", "kv_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim"))
    assert (moe_cfg.n_experts, moe_cfg.top_k, moe_cfg.n_shared, moe_cfg.d_ff,
            moe_cfg.n_group, moe_cfg.topk_group, moe_cfg.routed_scaling,
            moe_cfg.n_held, moe_cfg.held_first) == tuple(CONFIG[k] for k in (
                "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
                "moe_intermediate_size", "n_group", "topk_group",
                "routed_scaling_factor", "n_routed_experts_held",
                "first_held_expert"))
    # weights normalised over the chosen experts, rotary on interleaved
    # pairs: what the program always does
    assert CONFIG["norm_topk_prob"] and CONFIG["topk_method"] == "noaux_tc"
    assert moe_cfg.scoring == CONFIG["scoring_func"]
    assert arch.first_k_dense == CONFIG["first_k_dense_replace"]
    assert arch.norm_eps == CONFIG["rms_norm_eps"]
    y = arch.rope_scaling
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim) == (
        rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    # the share's size as the configuration file states it
    model = build_model(arch, no_dist())
    n = sum(x.size for x in jax.tree.leaves(model.abstract_params()))
    assert n * 2 / 1e9 == pytest.approx(8.65, abs=0.005)
    cache = jax.eval_shape(lambda: model.init_cache(None, None, 1, 1))
    assert sum(x.size * 2 for x in jax.tree.leaves(cache)) == 8064
    counts = ref.counts(CONFIG)
    assert counts.prefill(8192)[0] / 1e12 == pytest.approx(63.9, abs=0.05)
    assert counts.decode([1])[1] / 1e9 == pytest.approx(5.68, abs=0.01)


def test_weights_draw_the_new_leaves_at_their_scales():
    """bench/weights.py's rules reach the latent norms, the held experts,
    the dense layers and the routers' correction bias."""
    cfg = {**SMALL, "hidden_size": 256, "moe_intermediate_size": 128,
           "intermediate_size": 192}
    model = build_model(small_arch(cfg), no_dist())
    w = weights.make_weights(model, 11, jax.devices()[0])

    def std(a):
        return float(np.asarray(a, np.float64).std())

    for norm in ("q_norm", "kv_norm"):
        s = np.asarray(w["layers"]["attn"][norm]["scale"])
        assert s.min() >= 0.5 and s.max() <= 1.5
        assert abs(s.mean() - 1.0) < 0.1
    m = w["layers"]["moe"]
    for name, fan_in in (("gate", 256), ("up", 256), ("down", 128)):
        assert std(m[name]) == pytest.approx(fan_in ** -0.5, rel=0.05)
    assert std(m["bias"]) == pytest.approx(0.2, rel=0.15)
    dense = w["dense_layers"][0]
    assert std(dense["mlp"]["up"]["w"]) == pytest.approx(256 ** -0.5,
                                                         rel=0.05)
    assert std(dense["mlp"]["down"]["w"]) == pytest.approx(192 ** -0.5,
                                                           rel=0.05)
    assert std(dense["attn"]["wq_a"]["w"]) == pytest.approx(256 ** -0.5,
                                                            rel=0.05)
