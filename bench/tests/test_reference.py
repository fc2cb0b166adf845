"""The plain references compute what the served path computes: at a small
size in float32, prefill and then decode through the cache give the
reference's logits, for each model type."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights
from repro.dist.context import no_dist
from repro.launch.serve import serve_steps
from repro.models.api import build_model

SMALL = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
         "tie_word_embeddings": False, "torch_dtype": "float32",
         "serve_dtype": "float32"}
CONFIGS = {
    "qwen2": {**SMALL, "registry": "qwen1.5-0.5b", "model_type": "qwen2",
              "rope_theta": 1e6, "rms_norm_eps": 1e-5,
              "tie_word_embeddings": True},
    "starcoder2": {**SMALL, "registry": "starcoder2-15b",
                   "model_type": "starcoder2", "rope_theta": 1e5,
                   "norm_epsilon": 1e-5},
}


@pytest.mark.parametrize("model_type", sorted(CONFIGS))
def test_served_logits_match_the_reference(model_type):
    cfg = CONFIGS[model_type]
    P, steps = 24, 6
    model = build_model(harness.program_arch(cfg), no_dist())
    w = weights.make_weights(model, 2**35 + 9, jax.devices()[0])
    prefill, decode = serve_steps(model, P + steps)
    toks = np.random.default_rng(0).integers(0, 512, (1, P), np.int32)

    def logits(p, t):
        cache = model.init_cache(p, {"tokens": t}, 1, P + steps)
        return model.prefill(p, {"tokens": t}, cache)[0]

    served = [logits(w, toks)]
    tok, _, cache, lengths = prefill(w, toks)
    seq = list(toks[0])
    for _ in range(steps):
        seq.append(int(tok[0, 0]))
        step, _ = model.decode_step(w, cache, tok, lengths)
        served.append(step)
        tok, _, cache, lengths = decode(w, cache, tok, lengths)
    got = np.concatenate([np.asarray(s) for s in served])
    fwd = reference.model_module(cfg).forward
    S = reference.BLOCK
    ref = fwd(w, jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32), cfg,
              reference.dense_f32, P - 1, steps + 1)
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-4, atol=2e-4)
