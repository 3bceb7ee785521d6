"""Mamba2-2.7B [arXiv:2405.21060; hf:state-spaces/mamba2-2.7b config.json].

64 attention-free SSD layers, d_model 2560 (d_inner 5120, 80 heads of 64,
state 128, one group, conv 4, chunk 256), RMSNorm eps 1e-5, embeddings
tied and not scaled.  The vocabulary is 50277 tokens padded to a multiple
of 16 (``pad_vocab_size_multiple``), 50288.  O(1) decode state ⇒
long_500k is native.
"""

from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="mamba2-2.7b",
    arch_type="ssm",
    source="arXiv:2405.21060",
    num_layers=64,
    d_model=2_560,
    num_heads=1,                 # unused (attention-free)
    num_kv_heads=1,
    d_ff=0,
    vocab_size=50_288,
    attention_kind="none",
    rope_kind="none",
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                  chunk_size=256),
    norm_eps=1e-5,
    tie_embeddings=True,
    scale_embeddings=False,
    fed_agent_layout="sharded",
)
