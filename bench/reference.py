"""Plain float32 reference and the comparison that decides ``correct``.

The reference of a configuration is ``bench/models/<model_type>.py``: the
architecture's forward pass over one sequence in straightforward
``jax.numpy``, built from the operations below. It imports nothing of the
program. Every matrix product runs in float32 at ``Precision.HIGHEST``
(on a TPU a float32 product is otherwise computed in bfloat16), through
the ``dense`` it is given. The controls pass ``dense_int8`` or
``dense_fp8`` in its place: the two precisions just below the bfloat16
that the configurations state.

The comparison follows ``chip_smoke.reference_gaps``: a served token's
gap is how far its reference logit lies below the reference's best at
that position, in units of the standard deviation of that position's
reference logits. The number compared is the widest gap over the
requests checked.
"""
from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# query rows per block of the reference attention, so that the score
# matrix of one block of a long sequence fits beside the weights
BLOCK = 128


def dense_f32(x, w):
    """``x @ w`` in float32 at the highest precision."""
    return jnp.einsum("...i,io->...o", x.astype(F32), w.astype(F32),
                      precision=HIGHEST)


def dense_int8(x, w):
    """``x @ w`` as an int8 path would compute it: each row of ``x`` and
    each column of ``w`` scaled to int8 by its largest magnitude, the
    products summed in int32."""
    x, w = x.astype(F32), w.astype(F32)
    xs = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0
    ws = jnp.max(jnp.abs(w), 0, keepdims=True) / 127.0
    xs, ws = jnp.where(xs > 0, xs, 1.0), jnp.where(ws > 0, ws, 1.0)
    xq = jnp.round(x / xs).astype(jnp.int8)
    wq = jnp.round(w / ws).astype(jnp.int8)
    y = jnp.einsum("...i,io->...o", xq, wq,
                   preferred_element_type=jnp.int32)
    return y.astype(F32) * xs * ws


def dense_fp8(x, w):
    """``x @ w`` as an fp8 path would compute it: each row of ``x`` and
    each column of ``w`` scaled to float8_e4m3fn's largest value (448)
    by its largest magnitude and rounded to it, the products summed in
    float32."""
    x, w = x.astype(F32), w.astype(F32)
    xs = jnp.max(jnp.abs(x), -1, keepdims=True) / 448.0
    ws = jnp.max(jnp.abs(w), 0, keepdims=True) / 448.0
    xs, ws = jnp.where(xs > 0, xs, 1.0), jnp.where(ws > 0, ws, 1.0)
    xq = (x / xs).astype(jnp.float8_e4m3fn).astype(F32)
    wq = (w / ws).astype(jnp.float8_e4m3fn).astype(F32)
    y = jnp.einsum("...i,io->...o", xq, wq, precision=HIGHEST)
    return y * xs * ws


CONTROLS = {"int8": dense_int8, "fp8": dense_fp8}


def bias(p):
    """A dense layer's bias, or 0 where the layer has none."""
    return p["b"].astype(F32) if "b" in p else 0.0


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def layer_norm(x, scale, shift, eps):
    x = x.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(F32) \
        + shift.astype(F32)


def rope(x, theta):
    """Rotary embedding of ``x [S, heads, hd]`` at positions 0..S-1, in
    the rotate-half form of the published implementations."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_attention(q, k, v):
    """Softmax attention of ``q [S, H, hd]`` over ``k, v [S, KV, hd]``
    (grouped heads), causal, in blocks of ``BLOCK`` query rows."""
    S, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    nb = S // BLOCK
    qb = q.reshape(nb, BLOCK, KV, G, hd)

    def one(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HIGHEST) \
            / math.sqrt(hd)
        qpos = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(qpos[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(one, (qb, jnp.arange(nb)))
    return o.reshape(S, H, hd)


def gaps(ref, chosen):
    """Per position: how far the logit of ``chosen`` lies below the best
    of ``ref [n, V]``, in units of the row's standard deviation."""
    pick = jnp.take_along_axis(ref, chosen[:, None], 1)[:, 0]
    return (ref.max(-1) - pick) / ref.std(-1)


def model_module(cfg: dict):
    """The plain reference of a configuration, found by its model type."""
    return importlib.import_module(f"bench.models.{cfg['model_type']}")


class Checker:
    """Compares served tokens with the reference, one request at a time,
    at one compiled shape: prompt of ``prompt_len`` and up to ``max_new``
    served tokens, the sequence padded to a whole number of blocks."""

    def __init__(self, cfg: dict, prompt_len: int, max_new: int):
        self.P, self.M = prompt_len, max_new
        self.S = -(-(prompt_len + max_new) // BLOCK) * BLOCK
        fwd = model_module(cfg).forward

        def logits(w, toks, dense):
            return fwd(w, toks, cfg, dense, prompt_len - 1, max_new)

        self._program = jax.jit(
            lambda w, toks, served: gaps(logits(w, toks, dense_f32), served))

        def control(w, toks, served):
            ref = logits(w, toks, dense_f32)
            return gaps(ref, served), {
                name: gaps(ref, jnp.argmax(logits(w, toks, dense), -1))
                for name, dense in CONTROLS.items()}

        self._control = jax.jit(control)

    def _inputs(self, prompt: np.ndarray, served: list):
        n = len(served)
        toks = np.zeros(self.S, np.int32)
        toks[:self.P] = prompt.reshape(-1)
        toks[self.P:self.P + n - 1] = served[:-1]
        out = np.zeros(self.M, np.int32)
        out[:n] = served
        return toks, out, n

    def program_gaps(self, w, prompt, served) -> np.ndarray:
        """Gaps of the served tokens of one request."""
        toks, out, n = self._inputs(prompt, served)
        return np.asarray(self._program(w, toks, out))[:n]

    def control_gaps(self, w, prompt, served):
        """Gaps of the served tokens, and per control (``CONTROLS``) of
        the tokens that it puts first at the same positions."""
        toks, out, n = self._inputs(prompt, served)
        g, c = self._control(w, toks, out)
        return np.asarray(g)[:n], {k: np.asarray(v)[:n] for k, v in c.items()}


def sample(finished: dict, seed: int, min_tokens: int) -> list:
    """Request ids to compare, drawn from the seed: the one with the most
    served tokens, then others in a seeded order until ``min_tokens``
    served tokens are covered. ``finished`` maps rid -> token count."""
    if not finished:
        return []
    first = max(sorted(finished), key=lambda r: finished[r])
    rest = [r for r in sorted(finished) if r != first]
    order = np.random.default_rng([seed % 2**64, 4]).permutation(len(rest))
    out, total = [first], finished[first]
    for i in order:
        if total >= min_tokens:
            break
        out.append(rest[i])
        total += finished[rest[i]]
    return out
