"""deepseek-v3-671b — MoE with MLA [arXiv:2412.19437;
hf:deepseek-ai/DeepSeek-V3 config.json].

61 layers: the first 3 with a dense SwiGLU MLP of 18,432, the other 58
with 256 routed experts of 2,048 (8 per token) and 1 shared expert.
MLA (q_lora 1536 / kv_lora 512 / rope 64 / nope 128 / v 128, 128 heads),
rotary on interleaved pairs with YaRN scaling (factor 40 over 4,096
positions). Routing is ``noaux_tc``: sigmoid scores plus a correction
bias for choosing, 8 groups of 32 experts of which the best 4 are kept,
weights normalised over the 8 chosen and scaled by 2.5. RMSNorm eps 1e-6.
MTP (multi-token prediction) is an optional extra head
(``models.transformer.mtp_head``), exercised by its own test and not
served.

``EP32`` is one chip's share of a deployment that divides every MoE
layer over 32 chips by expert parallelism, with data-parallel attention:
the model above with experts 0-7 of each MoE layer held here (chip 0's
block). The router keeps all 256 outputs, its groups and its top-8; the
layer computes its held experts' terms and the shared expert, and what
the other 31 chips' experts would add is left out. The layers this
chip does not hold lie on further chips, as pipeline stages.
"""
import dataclasses

from repro.configs.base import ArchConfig, MoEConfig, MLAConfig, YarnConfig

CONFIG = ArchConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    kv_heads=128,            # MLA: kv_heads == n_heads after decompression
    d_ff=18432,              # dense MLP of the first_k_dense layers
    vocab=129280,
    act="silu",
    glu=True,
    norm="rmsnorm",
    norm_eps=1e-6,
    attention="mla",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    first_k_dense=3,
    moe=MoEConfig(n_experts=256, top_k=8, n_shared=1, d_ff=2048,
                  scoring="sigmoid", n_group=8, topk_group=4,
                  routed_scaling=2.5),
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  rope_head_dim=64, nope_head_dim=128, v_head_dim=128),
)

EP32 = dataclasses.replace(
    CONFIG, name="deepseek-v3-671b-ep32",
    moe=dataclasses.replace(CONFIG.moe, n_held=8, held_first=0),
    notes="one chip of EP32: experts 0-7 of each MoE layer held here")
