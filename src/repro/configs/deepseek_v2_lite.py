"""deepseek-v2-lite [hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434] —
27 layers, latent attention (kv_lora_rank 512, no query compression, YaRN
rope x40), layer 0 dense (SwiGLU 10,944), layers 1-26 with 64 routed
experts of width 1,408, top-6 by softmax without renormalization, and 2
shared experts (one SwiGLU 2,816 wide).  15,706,484,224 parameters."""
from repro.models.api import ModelConfig
from repro.models.blocks import YaRN

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400,
    n_experts=64, top_k=6, d_ff_shared=2816,
    first_k_dense=1, d_ff_dense=10944,
    norm_topk_prob=False, routed_scaling=1.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=1e4,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    norm_eps=1e-6,
)


def smoke_config() -> ModelConfig:
    """The same layer kinds: one dense layer, then expert layers with
    latent attention, shared experts and unnormalized top-k gates."""
    return CONFIG.replace(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                          d_ff=32, d_ff_shared=64, d_ff_dense=96,
                          n_experts=16, top_k=4, vocab=128,
                          kv_lora_rank=32, qk_nope_head_dim=16,
                          qk_rope_head_dim=8, v_head_dim=16,
                          rope_scaling=YaRN(factor=40.0,
                                            original_max_position=64,
                                            beta_fast=32.0, beta_slow=1.0,
                                            mscale=0.707, mscale_all_dim=0.707),
                          dtype="float32", remat=False)
