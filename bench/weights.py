"""Random weights from the run's seed, made on the device in one jitted call.

The benchmark makes the weights, so the plain reference uses the
benchmark's own arrays and nothing the program made. The tree has the
program's layout (``jax.eval_shape`` of its ``init``), each leaf filled by
a rule on its name and in the dtype it is served in. The stacked layers
are filled one layer at a time (``lax.map``), so the float32 transient is
one layer's, not the model's.

Rules, with ``d_in`` a matrix's first dimension: a dense ``w`` is uniform
with standard deviation ``1/sqrt(d_in)``; a dense bias ``b`` has standard
deviation 0.05; a norm's ``scale`` is uniform on [0.5, 1.5] and its
``bias`` has standard deviation 0.2; an untied ``unembed`` has standard
deviation ``1/sqrt(d_model)``. Any other leaf is treated as a dense matrix.

The scales keep what is served a function of each request's own tokens,
so that the greedy tokens of one request differ from step to step and a
fault or a lower precision changes some of them. A value bias passes
through attention unchanged wherever attention is spread out, so a large
one adds the same vector at every position and the model settles on one
token per request; 0.05 keeps it small. The embedding carries the
current token into the residual: standard deviation 1 where the
unembedding is untied, and 0.1 where it is tied, so that the logit of
the input token itself does not stand out and the model does not repeat
its input.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# standard deviation of the embedding, with a tied and an untied
# unembedding (see above)
EMBED_STD = {True: 0.1, False: 1.0}
BIAS_STD = 0.05


def key_from_seed(seed: int):
    """A PRNG key from any seed below 2**64: ``jax.random.key`` keeps
    only the low 32 bits, so the high bits are folded in."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _uniform(key, shape, std, mean=0.0):
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, mean - a, mean + a)


def _leaf(key, path, s, tied: bool):
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    shape = s.shape
    if name == "scale":
        v = _uniform(key, shape, 0.5 / math.sqrt(3.0), 1.0)
    elif name == "bias":
        v = _uniform(key, shape, 0.2)
    elif name == "b":
        v = _uniform(key, shape, BIAS_STD)
    elif name == "embed":
        v = _uniform(key, shape, EMBED_STD[tied])
    elif name == "unembed":
        v = _uniform(key, shape, 1.0 / math.sqrt(shape[-1]))
    else:
        v = _uniform(key, shape, 1.0 / math.sqrt(shape[0]))
    return v.astype(s.dtype)


def _fill(key, tree, tied: bool):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(treedef, [
        _leaf(jax.random.fold_in(key, i), path, s, tied)
        for i, (path, s) in enumerate(leaves)])


def make_weights(model, seed: int, device):
    """The model's parameters, drawn from ``seed`` on ``device``."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    layers = shapes["layers"]
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    one_layer = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), layers)
    rest = {k: v for k, v in shapes.items() if k != "layers"}
    tied = "unembed" not in rest

    def gen(key):
        k_layers, k_rest = jax.random.split(key)
        stacked = jax.lax.map(lambda k: _fill(k, one_layer, tied),
                              jax.random.split(k_layers, n_layers))
        return {**_fill(k_rest, rest, tied), "layers": stacked}

    key = jax.device_put(key_from_seed(seed), device)
    return jax.block_until_ready(jax.jit(gen)(key))
