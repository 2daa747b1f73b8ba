"""deepseek-v2-lite-16b [moe] -- MLA kv_lora=512 [arXiv:2405.04434].

27L d_model=2048 16H (kv=16) per-expert d_ff=1408 vocab=102400.
MoE: 64 routed experts top-6 (softmax scores, greedy, gates NOT
renormalized: ``norm_topk_prob`` false) + 2 shared, first layer dense
(d_ff=10944).
NOTE: the assignment line mentions both "64e top-6" and "160 routed"; 160
routed belongs to full DeepSeek-V2 -- V2-Lite (hf config) is 64 routed +
2 shared, top-6, which we follow (recorded in DESIGN.md).
MLA: kv_lora_rank=512, rope_head_dim=64, nope=128, v_head=128, no q-lora;
YaRN rope (factor 40 over 4096 original positions), whose mscale^2 also
scales the softmax.

The registered config holds all 64 experts. A chip's share of an
expert-parallel deployment sets ``experts_held`` and ``first_expert``:
the router keeps its 64 outputs and the layer computes its held experts'
part of the result.
"""

from repro.configs.base import ModelConfig, YarnScaling, register

CONFIG = register(
    ModelConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,  # dense first layer FFN width
        vocab=102400,
        n_routed_experts=64,
        n_shared_experts=2,
        top_k=6,
        d_expert=1408,
        first_k_dense=1,
        norm_topk_prob=False,
        use_mla=True,
        kv_lora_rank=512,
        q_lora_rank=0,
        rope_head_dim=64,
        nope_head_dim=128,
        v_head_dim=128,
        rope_theta=1e4,
        rope_scaling=YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                                 beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        act="silu",
        notes="MLA latent KV cache; YaRN rope; 64 routed experts, a share held per chip; "
              "long_500k skipped",
    )
)
