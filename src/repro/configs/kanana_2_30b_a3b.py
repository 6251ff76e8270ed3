"""kanana-2-30b-a3b-instruct-2601 — DeepSeek-V3 layers
(``model_type`` deepseek_v3) [hf:kakaocorp/kanana-2-30b-a3b-instruct-2601,
config.json]. 48 layers at hidden 2048, the first one dense (FF 6144);
MLA with 32 heads, queries not compressed, latent 512, head dims
128 + 64 (rope, interleaved channel pairs, θ 1e6, no scaling) and
values 128; 128 routed experts of width 768, top 6, with 2 shared
experts. Routing is noaux_tc with one group: selection on the sigmoid
score plus a correction bias, gates the chosen sigmoid scores
normalised to sum 1 and scaled by 2.448; no auxiliary loss.
Vocabulary 128,256, untied, RMSNorm eps 1e-6."""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig


def get_config() -> ArchConfig:
    return ArchConfig(
        name="kanana-2-30b-a3b",
        family="moe",
        n_layers=48,
        d_model=2048,
        n_heads=32,
        n_kv_heads=32,
        head_dim=128,            # v head dim; MLA dims below
        d_ff=768,                # routed-expert FF width
        vocab_size=128256,
        rope_theta=1e6,
        norm_eps=1e-6,
        moe=MoEConfig(n_experts=128, top_k=6, expert_ff=768, n_shared=2,
                      scoring="sigmoid", router_bias=True,
                      routed_scaling=2.448, norm_topk=True,
                      aux_loss=0.0, router_zloss=0.0),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                      v_dim=128, q_lora_rank=None, rope_interleave=True),
        first_k_dense=1,
        dense_ff=6144,
        citation="hf:kakaocorp/kanana-2-30b-a3b-instruct-2601",
    )
