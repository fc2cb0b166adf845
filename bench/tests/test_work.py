"""Work counts of both configurations against hand arithmetic."""
import json

from bench import reference
from conftest import ROOT


def counts(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return reference.model_module(cfg).counts(cfg)


def test_qwen_counts():
    c = counts("qwen1.5-0.5b")
    # per layer: q, k, v, o of 1024 x 1024, and gate, up, down of 1024 x 2816
    layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert c.matmul_params == 24 * layer == 308_281_344
    # 16 KV heads of 64, keys and values, 24 layers, bf16
    assert c.kv_bytes_per_token == 98_304
    # layers, q/k/v biases, two RMSNorm scales a layer, the final one, and
    # the tied unembedding: 0.93 GB
    params = 308_281_344 + 24 * (3 * 1024 + 2 * 1024) + 1024 + 151_936 * 1024
    assert c.weight_bytes == 2 * params == 927_975_424
    flops, nbytes = c.prefill(1024)
    assert flops == (2 * 308_281_344 * 1024
                     + 4 * 24 * 16 * 64 * (1024 * 1025 // 2)
                     + 2 * 1024 * 151_936)
    assert nbytes == 927_975_424 + 1024 * (98_304 + 2 * 1024)
    flops, nbytes = c.decode([1100])
    assert flops == 2 * 308_281_344 + 4 * 24 * 16 * 64 * 1100 + 2 * 1024 * 151_936
    assert nbytes == 927_975_424 + 1100 * 98_304 + 2 * 1024
    # a round of three sequences reads the weights once
    flops, nbytes = c.decode([1100, 1030, 1500])
    assert flops == 3 * (2 * 308_281_344 + 2 * 1024 * 151_936) \
        + 4 * 24 * 16 * 64 * (1100 + 1030 + 1500)
    assert nbytes == 927_975_424 + (1100 + 1030 + 1500) * 98_304 + 3 * 2 * 1024


def test_starcoder2_stage_counts():
    c = counts("starcoder2-15b-pp4")
    # per layer: q and o of 6144 x 6144, k and v of 6144 x 512, and the
    # plain MLP's up and down of 6144 x 24576
    layer = 2 * 6144 * 6144 + 2 * 6144 * 512 + 2 * 6144 * 24576
    assert c.matmul_params == 10 * layer == 3_837_788_160
    # 4 KV heads of 128, keys and values, 10 layers, bf16
    assert c.kv_bytes_per_token == 20_480
    biases = 6144 + 512 + 512 + 6144 + 24576 + 6144
    norms = 2 * 2 * 6144                          # LayerNorm scale and bias
    params = 3_837_788_160 + 10 * (biases + norms) + 2 * 6144 + 49_152 * 6144
    assert c.weight_bytes == 2 * params
    # with the embedding, the stage holds 4.44 B parameters
    assert round((params + 49_152 * 6144) / 1e9, 2) == 4.44
    flops, _ = c.prefill(2048)
    assert flops == (2 * 3_837_788_160 * 2048
                     + 4 * 10 * 48 * 128 * (2048 * 2049 // 2)
                     + 2 * 6144 * 49_152)
    # 16.2 TFLOP: 82 ms at 197 TFLOP/s
    assert round(flops / 1e12, 1) == 16.2
    flops, nbytes = c.decode([2100])
    assert flops == 2 * 3_837_788_160 + 4 * 10 * 48 * 128 * 2100 + 2 * 6144 * 49_152
    assert nbytes == 2 * params + 2100 * 20_480 + 2 * 6144
