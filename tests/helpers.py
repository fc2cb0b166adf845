"""Test helpers: run a snippet in a subprocess with N fake XLA devices
(the main test process must keep seeing exactly one device), find the
copies of a KV cache or of a weight in compiled HLO text, and fix the
service times an executor reports to the engine."""
import contextlib
import os
import re
import subprocess
import sys
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parent.parent


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout[-4000:]}\n"
            f"STDERR:\n{out.stderr[-4000:]}")
    return out.stdout


def cache_copies(hlo: str, cache_shape) -> list:
    """The instructions of compiled HLO text that copy a whole cache of
    ``cache_shape`` ``[L, B, S, ...]``, or one layer's slice of it: copies,
    fusions named for a copy, and materialised dynamic slices."""
    L, *rest = cache_shape
    shapes = {",".join(map(str, s)) for s in
              ((L, *rest), (1, *rest), tuple(rest))}
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        copies = (op.startswith("copy") or name.startswith("copy")
                  or "dynamic-slice" in name)
        dims = set(re.findall(r"\[([\d,]*)\]", shape))
        if copies and dims & shapes:
            out.append(line.strip()[:160])
    return out


# an array's shape, layout and tiling/memory space in HLO text
_ARRAY = re.compile(r"\w+\[([\d,]*)\]\{([\d,]*)(?::([^}]*))?\}")


def weight_copies(hlo: str, params, min_bytes: int = 8 * 2**20) -> list:
    """The instructions of compiled HLO text that copy a leaf of
    ``params`` over ``min_bytes``, or one layer's slice of a stacked leaf
    ``[L, ...]``: copies, fusions named for a copy, and materialised
    dynamic slices, of the leaf's own shape (a stacked leaf's also
    ``[1, ...]`` and ``[...]``). A 2-D leaf matches by its exact shape
    only, so that a bias as long as a matrix is wide does not. A copy into
    the chip's fast memory (``S(1)``) that keeps the default layout is how
    the compiler stages any read of a weight and is not counted; a copy
    into another layout, or into HBM, is."""
    shapes = set()
    for w in jax.tree.leaves(params):
        if w.size * w.dtype.itemsize > min_bytes:
            shapes.add(tuple(w.shape))
            if w.ndim > 2:
                shapes |= {tuple(w.shape[1:]), (1, *w.shape[1:])}
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        a = _ARRAY.search(shape)
        if not a or not (op.startswith("copy") or name.startswith("copy")
                         or "dynamic-slice" in name):
            continue
        dims = tuple(int(d) for d in a.group(1).split(",") if d)
        default = a.group(2) == ",".join(map(str, range(len(dims) - 1, -1, -1)))
        staged = default and "S(1)" in (a.group(3) or "")
        if dims in shapes and not staged:
            out.append(line.strip()[:160])
    return out


def assert_updates_cache_in_place(compiled, cache):
    """``compiled``, a step given ``cache`` (arrays or shapes: one cache
    or a sequence of them), aliases it to its output and copies neither
    it nor a layer's slice of it."""
    hlo = compiled.as_text()
    assert "input_output_alias" in hlo.splitlines()[0]
    leaves = jax.tree.leaves(cache)
    for c in leaves:
        assert cache_copies(hlo, c.shape) == []
    cache_bytes = sum(c.size * c.dtype.itemsize for c in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


@contextlib.contextmanager
def reported_times(ex, prefill_ms: float = 1.0, decode_ms: float = 3.0):
    """``ex``, an engine's executor, reporting each prefill it runs as
    ``prefill_ms`` and each decode round as ``decode_ms``, whatever they
    took: the engine's schedule, and so its rounds, then do not hang on
    how fast this machine runs the steps."""
    prefill, decode = ex.prefill, ex.decode

    def timed_prefill(*args):
        return prefill_ms if prefill(*args) else 0.0

    def timed_decode(*args):
        decode(*args)
        return decode_ms

    ex.prefill, ex.decode = timed_prefill, timed_decode
    try:
        yield ex
    finally:
        del ex.prefill, ex.decode


class Rounds:
    """An engine oracle that records the size of every decode round the
    engine runs, and ignores every other event."""

    def __init__(self):
        self.sizes = []

    def on_decode(self, t0, t1, pool, batch):
        self.sizes.append(len(batch))

    def __getattr__(self, name):
        return lambda *args: None
