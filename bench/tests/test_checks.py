"""The comparison that decides ``correct``, on the CPU: a sound run
passes; the program broken underneath the timed path fails; and the fp8
control fails the limits where the program passes."""
import time

import jax
import pytest

from bench import harness
from conftest import make_tiny_root
from repro.launch import serve


def run(root, seed=2**32 + 3, seconds=1.5):
    return harness.run_cell(root, harness.load_spec(root), "tiny.mix", seed,
                            seconds, False, time.perf_counter(),
                            jax.devices()[0], {})


def altered_token(monkeypatch):
    """Every greedy token is replaced by the next id where it is made."""
    greedy = serve._greedy

    def wrong(logits):
        tok, ok = greedy(logits)
        return (tok + 1) % logits.shape[-1], ok

    monkeypatch.setattr(serve, "_greedy", wrong)


def state_unchanged(monkeypatch):
    """The decode step returns the cache it was given."""
    steps = serve.serve_steps

    def stale(model, max_seq):
        prefill, decode = steps(model, max_seq)

        def decode_stale(p, cache, tok, lengths):
            tok, ok, _, lengths = decode(p, cache, tok, lengths)
            return tok, ok, cache, lengths

        return prefill, decode_stale

    monkeypatch.setattr(serve, "serve_steps", stale)


def half_batch(monkeypatch):
    """A decode round advances only the first half of its batch (none of
    a batch of one)."""
    decode = serve.RealModelExecutor.decode

    def half(self, batch, pool, ndev):
        return decode(self, batch[:len(batch) // 2], pool, ndev)

    monkeypatch.setattr(serve.RealModelExecutor, "decode", half)


def test_sound_run_is_correct(tmp_path):
    out = run(make_tiny_root(tmp_path))
    assert out["correct"], out["checks"]
    assert out["checks"]["max_gap_std"]["value"] <= 0.5


@pytest.mark.parametrize("fault, failing", [
    (altered_token, "max_gap_std"),
    (state_unchanged, "max_gap_std"),
    (half_batch, "accounting_errors"),
])
def test_fault_underneath_makes_the_run_incorrect(tmp_path, monkeypatch,
                                                  fault, failing):
    fault(monkeypatch)
    out = run(make_tiny_root(tmp_path))
    assert not out["correct"]
    c = out["checks"][failing]
    assert c["value"] > c["limit"], out["checks"]


# A bfloat16 qwen2 of width 128 with 65,536 tokens. The limits lie
# between the program's readings and the fp8 control's on seeds 0-5: the
# program's widest gap 0.011-0.026 and mean 1.4e-4-5.7e-4, the fp8
# control's 0.36-0.47 and 0.030-0.041. The int8 control reads 0.062-0.12
# and 1.7e-3-4.2e-3, within three times the program's, and sets no limit.
MID = {"hidden_size": 128, "intermediate_size": 256, "vocab_size": 65536,
       "check": {"min_tokens": 200, "max_gap_std": 0.1,
                 "mean_gap_std": 0.004}}
MID_MIX = {"prompt_tokens": 64,
           "arrivals": {"process": "poisson", "rate_per_s": 4.0},
           "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                             "min": 8, "max": 48}}


def test_fp8_control_fails_where_the_program_passes(tmp_path):
    root = make_tiny_root(tmp_path, MID, MID_MIX)
    s = harness.Session(root, harness.load_spec(root), "tiny.mix", 0,
                        jax.devices()[0])
    lim = s.cfg["check"]
    for seed in (0, 1, 2):
        s.set_seed(seed)
        g = s.gaps(s.drive(4.0), control=True)
        assert g["n"] >= 200 and g["fp8_n"] == g["n"]
        assert harness.passed(harness.gap_checks(lim, g))
        fp8 = harness.gap_checks(lim, g, "fp8_")
        assert fp8["max_gap_std"]["value"] > lim["max_gap_std"]
        assert fp8["mean_gap_std"]["value"] > lim["mean_gap_std"]
        assert not harness.passed(fp8)
