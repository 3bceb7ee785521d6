"""DeepSeek-V3 671B [arXiv:2412.19437].

61L, d_model 7168, 128 heads with MLA (kv_lora 512, q_lora 1536,
qk 128 nope + 64 rope, v 128); MoE with 1 shared + 256 routed experts,
top-8, expert d_ff 2048 (first 3 layers dense, d_ff 18432); vocab 129280.
MTP (multi-token prediction) is not implemented.

671B params ⇒ federated agents cannot hold replicas: the framework uses the
``replicated`` agent layout (4 cross-silo agents, each agent's state
FSDP-sharded over the full data×model mesh) per DESIGN §3.
"""

import jax.numpy as jnp

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="deepseek-v3-671b",
    arch_type="moe",
    source="arXiv:2412.19437",
    num_layers=61,
    d_model=7_168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=18_432,                 # dense-layer FFN width
    vocab_size=129_280,
    attention_kind="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1_536,
                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    moe=MoEConfig(num_experts=256, num_shared=1, top_k=8,
                  d_ff_expert=2_048, capacity_factor=1.25,
                  first_dense_layers=3, d_ff_dense=18_432),
    long_context_window=4_096,
    mlp_kind="swiglu",
    param_dtype=jnp.bfloat16,  # >100B: bf16 SGD state (DESIGN §3)
    fed_agent_layout="replicated",
    fed_n_agents_replicated=1,
)
