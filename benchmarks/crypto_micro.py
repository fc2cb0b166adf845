"""ChaCha20 kernel microbenchmark (paper §1: 2.89 GB/s AVX-512 vs
1.6 GB/s AVX2). Reports us_per_call of the Pallas kernel and of the jnp
reference on the default backend. The kernel runs compiled on a TPU and
through the Pallas interpreter elsewhere; its row is named after the
mode that ran, and the GB/s column after the backend."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.chacha20 import keystream
from repro.kernels.ref import chacha20_keystream_ref


def rows():
    key = jnp.arange(8, dtype=jnp.uint32)
    nonce = jnp.asarray([1, 2, 3], dtype=jnp.uint32)
    n = 1024                               # 64 KiB of keystream
    backend = jax.default_backend()
    interpret = backend != "tpu"
    mode = "pallas_interpret" if interpret else "pallas_compiled"
    out = []
    for name, fn in (
        (mode, lambda: keystream(key, nonce, 1, n_blocks=n, tile=256,
                                 interpret=interpret)),
        ("jnp_ref",
         lambda: jax.jit(lambda: chacha20_keystream_ref(key, nonce, 1, n))()),
    ):
        jax.block_until_ready(fn())        # compile outside the timing
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) * 1e6 / reps
        gbps = n * 64 / (us / 1e6) / 1e9
        out.append((f"crypto_micro[{name}]", us, f"{gbps:.3f}GB/s_{backend}"))
    return out
