"""Test helpers: run a snippet in a subprocess with N fake XLA devices
(the main test process must keep seeing exactly one device), and find the
copies of a KV cache in compiled HLO text."""
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


def assert_updates_cache_in_place(compiled, cache):
    """``compiled``, a step given ``cache`` (arrays or shapes), aliases it
    to its output and copies neither it nor a layer's slice of it."""
    hlo = compiled.as_text()
    assert "input_output_alias" in hlo.splitlines()[0]
    leaves = jax.tree.leaves(cache)
    for c in leaves:
        assert cache_copies(hlo, c.shape) == []
    cache_bytes = sum(c.size * c.dtype.itemsize for c in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
