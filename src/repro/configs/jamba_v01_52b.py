"""jamba-v0.1-52b [hybrid] — Mamba+attn 1:7 interleave, MoE
[arXiv:2403.19887; hf].

Layer i is attention when ``i % 8 == 4`` and MoE when ``i % 2 == 1``
(``attn_layer_offset`` 4, ``expert_layer_offset`` 1); attention carries
no positional encoding; the Mamba mixer norms dt, B and C; the top-2
router weights are not renormalized, and no token is dropped."""
from repro.configs.base import ArchConfig, MoEConfig, HybridConfig

CONFIG = ArchConfig(
    name="jamba-v0.1-52b", family="hybrid",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336,
    vocab=65536, head_dim=128, rope=False, norm_eps=1e-6,
    moe=MoEConfig(n_experts=16, top_k=2, d_ff_expert=14336, moe_every=2,
                  moe_offset=1, capacity_factor=None, renormalize=False),
    hybrid=HybridConfig(period=8, attn_index=4, d_state=16, d_conv=4,
                        expand=2, dt_rank=256, ssm_norm=True),
    source="arXiv:2403.19887; hf",
)
