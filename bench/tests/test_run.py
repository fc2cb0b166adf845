"""A whole run of a small cell on the CPU, past the harness's look for a
chip: discovery of files by name, the result line, and the checks."""
import json
import subprocess
import sys
import time

import jax

from bench import harness
from conftest import ROOT


def run_tiny(root, seconds=1.5, seed=2**31 + 11):
    spec = harness.load_spec(root)
    return harness.run_cell(root, spec, "tiny.mix", seed, seconds, False,
                            time.perf_counter(), jax.devices()[0], {})


def test_files_added_in_a_copy_are_found_by_name(tiny_root):
    (tiny_root / "bench" / "metrics" / "tokens_served.py").write_text(
        "def read(run):\n    return float(sum(map(len, run.log.tokens.values())))\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "tokens_served", "unit": "tokens",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny.mix"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_tiny(tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 18 and out["failed"] == 0
    names = set(out["metrics"])
    assert {"tokens_served", "setup_s", "ttft_p90_ms", "itl_p50_ms",
            "itl_p99_ms"} <= names
    assert out["metrics"]["tokens_served"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen1.5-0.5b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, env={"JAX_PLATFORMS": "cpu",
                                       "PATH": "/usr/bin:/bin"}, timeout=300)
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout and "no TPU" in r.stderr

