"""Shared set-up of the benchmark's CPU tests: the repository root and
src/ on the path, JAX on the CPU, and a small copy of the benchmark."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CONFIG = {
    "source": "a small qwen2 for tests on the CPU",
    "registry": "qwen1.5-0.5b", "model_type": "qwen2",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "serve_dtype": "bfloat16",
    "reduced": ["hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_hidden_layers", "vocab_size"],
    "check": {"min_tokens": 24, "max_gap_std": 0.5, "mean_gap_std": 0.05},
}
TINY_MIX = {
    "loop": "open", "arrivals": {"process": "poisson", "rate_per_s": 12.0},
    "prompt_tokens": 16,
    "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                      "min": 2, "max": 8},
    "order_seed": 5,
}


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def make_tiny_root(tmp_path: Path, config: dict = None, mix: dict = None
                   ) -> Path:
    """A copy of BENCHMARK.json and bench/ in ``tmp_path`` with one small
    cell added, ``tiny.mix``, that reports every end-to-end and per-layer
    metric. ``config`` and ``mix`` update the small cell's files."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps({**TINY_CONFIG, **(config or {})}))
    (tmp_path / "bench" / "traffic" / "mix.json").write_text(
        json.dumps({**TINY_MIX, **(mix or {})}))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": TINY_CONFIG["reduced"], "why": "test"})
    spec["workloads"].append({"name": "tiny.mix", "config": "tiny",
                              "traffic": "mix", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.mix")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path
