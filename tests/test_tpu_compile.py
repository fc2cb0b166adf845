"""Compile the serve path's programs for a described TPU v5e.

No chip is attached: XLA's TPU compiler builds each program for
``v5e:2x2`` from shapes alone, and refuses what the chip would refuse
(tiling, scoped memory, a program larger than the device). The
topology is described inside a fixture, never while a module is
imported, so every test worker collects the same tests and only the one
that runs this file loads the TPU library. The persistent compilation
cache is off around these compiles: an entry compiled for a described
device cannot be read back without one.
"""
import dataclasses
import functools
import json
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from helpers import REPO, assert_updates_cache_in_place, weight_copies

sys.path.insert(0, str(REPO))

from bench.harness import program_arch  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.dist.context import no_dist  # noqa: E402
from repro.kernels.chacha20 import keystream  # noqa: E402
from repro.kernels.decode_attention import flash_decode  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.launch.serve import jit_steps, placed, round_sizes, serve_steps  # noqa: E402
from repro.models.api import build_model  # noqa: E402

HBM_BYTES = 16 * 2**30          # one v5e chip
PROMPT, MAX_SEQ = 1024, 1152     # qwen1.5-0.5b serve shapes, batch 1
SIZES = round_sizes(8)           # the rounds the executor compiles


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def qwen(one_chip):
    """qwen1.5-0.5b at its published width: the serve steps, and its
    parameters and prompt as shapes on one described chip."""
    model = build_model(get_arch("qwen1.5-0.5b"), no_dist())
    params = jax.tree.map(lambda s: placed(s, one_chip),
                          model.abstract_params())
    toks = placed(jax.ShapeDtypeStruct((1, PROMPT), jnp.int32), one_chip)
    return serve_steps(model, MAX_SEQ), params, toks


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_qwen_prefill_compiles(qwen):
    (prefill, _), params, toks = qwen
    _fits(jax.jit(prefill).lower(params, toks).compile())


def _round(decode, params, row, size):
    """``decode`` compiled for a round of ``size`` sequences, each with
    its own cache, token and length as ``row`` (prefill's output) has
    them, and the round's caches."""
    tok, _, cache, lengths = row
    caches = (cache,) * size
    return decode.lower(params, caches, (tok,) * size,
                        (lengths,) * size).compile(), caches


@pytest.mark.parametrize("size", SIZES)
def test_qwen_decode_step_compiles(qwen, one_chip, size):
    """Decode as the executor compiles it for a round of ``size``: each
    cache it donates is its output's buffer, and neither a cache nor a
    layer's slice of one is copied."""
    steps, params, toks = qwen
    prefill, decode = jit_steps(steps)
    row = jax.tree.map(lambda s: placed(s, one_chip),
                       jax.eval_shape(prefill, params, toks))
    compiled, caches = _round(decode, params, row, size)
    _fits(compiled)
    assert_updates_cache_in_place(compiled, caches)


# a decode round's temporaries: 6.5 MB for one sequence of the DeepSeek
# share and 7.8 to 11.6 MB for two to eight; the starcoder2 stage's under
# 2 MB. A copy of a layer's `wq_b` (75 MB), of its held experts (704 MB)
# or of one expert (88 MB) breaks the bound.
TEMP_BOUND = 16 * 2**20


def _stage(cfg, prompt: int, max_seq: int, sharding):
    """``cfg``'s parameters as shapes on ``sharding``, and its decode as
    the executor compiles it for a round of a given size after a prompt of
    ``prompt`` tokens: ``(compiled, caches)``, each size compiled once."""
    model = build_model(cfg, no_dist())
    params = jax.tree.map(lambda s: placed(s, sharding),
                          model.abstract_params())
    toks = placed(jax.ShapeDtypeStruct((1, prompt), jnp.int32), sharding)
    prefill, decode = jit_steps(serve_steps(model, max_seq))
    row = jax.tree.map(lambda s: placed(s, sharding),
                       jax.eval_shape(prefill, params, toks))
    return params, functools.cache(
        lambda size: _round(decode, params, row, size))


@pytest.fixture(scope="module")
def deepseek_share(one_chip):
    """The DeepSeek-V3 share at published widths (the benchmark cell's 3
    dense + 4 MoE layers and vocabulary slice), after a 1k prompt."""
    cfg = dataclasses.replace(get_arch("deepseek-v3-671b-ep32"), n_layers=7,
                              vocab=16160)
    return _stage(cfg, PROMPT, MAX_SEQ, one_chip)


@pytest.fixture(scope="module")
def starcoder2_stage(one_chip):
    """The starcoder2-15b pipeline stage of the benchmark cell (10 layers
    at published widths, built as the harness builds it), after its 2k
    prompts with room for their 128 tokens."""
    conf = json.loads((REPO / "bench" / "configs" / "starcoder2-15b-pp4.json")
                      .read_text())
    return _stage(program_arch(conf), 2048, 2048 + 128, one_chip)


@pytest.mark.parametrize("size", SIZES)
def test_deepseek_share_decode_reads_experts_in_place(deepseek_share, size):
    """The DeepSeek-V3 share's decode for a round of ``size``: every
    donated latent cache is updated in place, and no layer's held experts
    are copied. A layer loop that slices them out of the stack, or an
    expert axis in the tiled minor pair of a matrix, copies gigabytes
    every call."""
    _, rounds = deepseek_share
    compiled, caches = rounds(size)
    _fits(compiled)
    assert_updates_cache_in_place(compiled, caches)
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_BOUND


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("stage", ["starcoder2_stage", "deepseek_share"])
def test_decode_round_copies_no_weight(request, stage, size):
    """A decode round of ``size`` reads each weight where it lies: no
    weight over 8 MiB, nor a layer's slice of one, is copied, relaid out
    or materialised, at one row or at eight. From two rows on, a query
    projection whose output the attention reads head-major has the
    compiler transpose `wq` / `wq_b` in every layer; batched over rows,
    the latent attention's absorbed matmuls do the same to `wkv_b`."""
    params, rounds = request.getfixturevalue(stage)
    compiled, _ = rounds(size)
    assert weight_copies(compiled.as_text(), params) == []


def _kernel_hlo(fn, *shapes, sharding):
    args = [placed(s, sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 16, 2048, 64), jnp.bfloat16)
    hlo = _kernel_hlo(lambda q, k, v: flash_attention(q, k, v), q, q, q,
                      sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_flash_decode_compiles(one_chip):
    q = jax.ShapeDtypeStruct((8, 16, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((8, 16, 2048, 64), jnp.bfloat16)
    lengths = jax.ShapeDtypeStruct((8,), jnp.int32)
    hlo = _kernel_hlo(lambda q, k, v, n: flash_decode(q, k, v, n),
                      q, kv, kv, lengths, sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_chacha20_compiles(one_chip):
    key = jax.ShapeDtypeStruct((8,), jnp.uint32)
    nonce = jax.ShapeDtypeStruct((3,), jnp.uint32)
    hlo = _kernel_hlo(
        lambda k, n: keystream(k, n, 1, n_blocks=4096, tile=256),
        key, nonce, sharding=one_chip)
    assert "tpu_custom_call" in hlo
