"""codeqwen1.5-7b [dense] -- qwen1.5 architecture [hf:Qwen/CodeQwen1.5-7B].

32L d_model=4096 32H x 128 (GQA kv=4, group 8) d_ff=13440 vocab=92416,
QKV bias (qwen1.5 family uses attention QKV bias), untied head, SwiGLU,
RoPE theta 1e6, RMSNorm eps 1e-5.
"""

from repro.configs.base import ModelConfig, register

CONFIG = register(
    ModelConfig(
        name="codeqwen1.5-7b",
        family="dense",
        n_layers=32,
        d_model=4096,
        n_heads=32,
        n_kv_heads=4,
        d_head=128,
        d_ff=13440,
        vocab=92416,
        qkv_bias=True,
        rope_theta=1e6,
        rms_eps=1e-5,
        act="silu",
        notes="full-attention dense LM; long_500k skipped (quadratic attn)",
    )
)
