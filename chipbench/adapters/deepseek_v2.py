"""The benchmark's adapter for a DeepSeek-V2 decoder (``chipbench/adapters``):
the program's ``ModelConfig`` and parameter layout for it, and its
operations and bytes.

Configurations are the published ``config.json`` keys of DeepSeek-V2
(latent attention without query compression, YaRN rope, leading dense
layers, routed and shared experts), with ``n_routed_experts`` the experts
held here, ``n_routed_experts_published`` the router's width and
``first_held_expert`` the global id of the first held one.

The counts are written from the shapes, as ``adapters/qwen2.py``'s are.
Two multiply-adds count as two operations.  The latent cache holds, a token
a layer, the normed latent (``kv_lora_rank``) and the shared roped key part
(``qk_rope_head_dim``).
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.models.api import ModelConfig

# the program's latent attention and expert fields: a program without them
# is refused here, when the cell is loaded, before any device work (the
# command's RuntimeError exit, 2, as for an unknown architecture)
try:
    from repro.models.blocks import YaRN
except ImportError as e:
    raise RuntimeError(
        "the program has no latent attention (repro.models.blocks.YaRN), "
        "which a deepseek_v2 configuration needs") from e

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a DeepSeek-V2 ``config.json``."""
    y = config["rope_scaling"]
    h = int(config["num_attention_heads"])
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]), n_heads=h,
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["moe_intermediate_size"]),
        vocab=int(config["vocab_size"]),
        n_experts=int(config["n_routed_experts_published"]),
        top_k=int(config["num_experts_per_tok"]),
        d_ff_shared=(int(config["n_shared_experts"])
                     * int(config["moe_intermediate_size"])),
        first_k_dense=int(config["first_k_dense_replace"]),
        d_ff_dense=int(config["intermediate_size"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        expert_offset=int(config["first_held_expert"]),
        experts_held=int(config["n_routed_experts"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=YaRN(
            factor=float(y["factor"]),
            original_max_position=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"])),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config["torch_dtype"])


def _attention(lw: Dict, mcfg) -> Dict:
    H, nope = mcfg.n_heads, mcfg.qk_nope_head_dim
    r = mcfg.kv_lora_rank
    kv_b = lw["kv_b_proj"].reshape(lw["kv_b_proj"].shape[:-1] + (H, -1))
    return {"wq": lw["q_proj"], "wkv_a": lw["kv_a_proj"],
            "kv_norm": lw["kv_a_norm"],
            "w_uk": kv_b[..., :nope], "w_uv": kv_b[..., nope:],
            "wo": lw["o_proj"]}


def _swiglu(w: Dict) -> Dict:
    return {"w_gate": w["gate_proj"], "w_up": w["up_proj"],
            "w_down": w["down_proj"]}


def to_program_params(w: Dict, mcfg) -> Dict:
    """The reference's weights in the program's layout: the dense layers and
    the expert layers stacked apart, ``kv_b_proj`` split into the per-head
    ``w_uk`` and ``w_uv``, the router in float32 (as the program keeps it),
    and the vocabulary padded to the program's multiple (padded rows are
    zero; the engine drops their logits before it samples)."""
    pad = mcfg.padded_vocab - mcfg.vocab
    dense, moe = w["dense"], w["moe"]
    return {
        "embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
        "dense_layers": {
            "attn_norm": dense["input_norm"], "attn": _attention(dense, mcfg),
            "ffn_norm": dense["post_attention_norm"], "ffn": _swiglu(dense)},
        "layers": {
            "attn_norm": moe["input_norm"], "attn": _attention(moe, mcfg),
            "ffn_norm": moe["post_attention_norm"],
            "router": moe["gate"].astype(jnp.float32),
            "experts": _swiglu(moe["experts"]),
            "shared": _swiglu(moe["shared"])},
        "final_norm": w["final_norm"],
        "lm_head": jnp.pad(w["lm_head"], ((0, 0), (0, pad))),
    }


class Shapes:
    def __init__(self, config: Dict):
        self.d = int(config["hidden_size"])
        self.layers = int(config["num_hidden_layers"])
        self.dense_layers = int(config["first_k_dense_replace"])
        self.moe_layers = self.layers - self.dense_layers
        self.heads = int(config["num_attention_heads"])
        self.nope = int(config["qk_nope_head_dim"])
        self.rope = int(config["qk_rope_head_dim"])
        self.v = int(config["v_head_dim"])
        self.r = int(config["kv_lora_rank"])
        self.ff = int(config["intermediate_size"])
        self.expert_ff = int(config["moe_intermediate_size"])
        self.shared_ff = int(config["n_shared_experts"]) * self.expert_ff
        self.experts = int(config["n_routed_experts_published"])
        self.held = int(config["n_routed_experts"])
        self.top_k = int(config["num_experts_per_tok"])
        self.vocab = int(config["vocab_size"])
        self.dtype_bytes = DTYPE_BYTES[config["torch_dtype"]]

    # ------------------------------------------------------------ params
    @property
    def attn_params(self) -> int:
        """Projections of one attention layer, and the latent's norm."""
        H = self.heads
        return (self.d * H * (self.nope + self.rope)
                + self.d * (self.r + self.rope) + self.r
                + self.r * H * (self.nope + self.v) + H * self.v * self.d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.expert_ff

    def layer_params(self, experts: int) -> int:
        """One expert layer holding ``experts`` routed experts."""
        return (self.attn_params + 2 * self.d + self.d * self.experts
                + 3 * self.d * self.shared_ff + experts * self.expert_params)

    @property
    def dense_layer_params(self) -> int:
        return self.attn_params + 2 * self.d + 3 * self.d * self.ff

    def params_holding(self, experts: int) -> int:
        """Every parameter with ``experts`` routed experts a layer."""
        return (self.dense_layers * self.dense_layer_params
                + self.moe_layers * self.layer_params(experts)
                + 2 * self.vocab * self.d + self.d)

    @property
    def params(self) -> int:
        """The parameters held here."""
        return self.params_holding(self.held)

    def weight_bytes_per_call(self, rows: int) -> int:
        """Least weight bytes one forward call reads for ``rows`` rows:
        every parameter but the experts and the embedding rows (a lookup,
        taken as none), plus in each expert layer the held experts some row
        reaches, ``held * (1 - (1 - top_k/E)^rows)`` expected under uniform
        routing."""
        fixed = self.params_holding(0) - self.vocab * self.d
        reached = self.held * (1.0 - (1.0 - self.top_k / self.experts) ** rows)
        return int(round((fixed + self.moe_layers * reached * self.expert_params)
                         * self.dtype_bytes))

    # ------------------------------------------------------------ KV cache
    @property
    def kv_bytes_per_token(self) -> int:
        """The latent and the shared roped key, a layer."""
        return self.layers * (self.r + self.rope) * self.dtype_bytes

    # ------------------------------------------------------------ FLOPs
    @property
    def projection_flops(self) -> float:
        """Operations of one token's projections, the absorbed form: every
        projection but ``kv_b_proj``'s expansion of the keys and values
        (absorbed: W_uk into the query and W_uv out of the context, each
        ``H * nope * r`` or ``H * v * r`` multiply-adds), the router, the
        shared experts, and the routed experts at ``top_k * held / E``
        passes a token, their expectation under uniform routing."""
        H = self.heads
        attn = (self.d * H * (self.nope + self.rope)
                + self.d * (self.r + self.rope)
                + H * self.nope * self.r + H * self.v * self.r
                + H * self.v * self.d)
        expert = (self.d * self.experts + 3 * self.d * self.shared_ff
                  + self.top_k * self.held / self.experts * self.expert_params)
        dense = 3 * self.d * self.ff
        return 2.0 * (self.layers * attn + self.dense_layers * dense
                      + self.moe_layers * expert + self.vocab * self.d)

    def token_flops(self, keys: int) -> float:
        """Forward operations of one decode token that attends ``keys`` keys
        in the absorbed form: ``2 * H * (r + rope + r)`` a key a layer
        (scores against the latent and the roped key, the weighted sum of
        the latent)."""
        attend = 2.0 * self.heads * (self.r + self.rope + self.r)
        return self.projection_flops + self.layers * attend * keys

    def span_flops(self, start: int, n: int) -> float:
        """Forward operations of ``n`` tokens at positions ``start`` ..
        ``start + n - 1`` in the expanded form, as prefill computes them:
        each token's projections (its own key and value expanded from its
        latent, as many multiply-adds as the absorption's), and attention
        over per-head keys of ``nope + rope`` and values of ``v``, each
        token attending causally to itself and all before."""
        keys = n * start + n * (n + 1) / 2.0
        attend = 2.0 * self.heads * (self.nope + self.rope + self.v)
        return self.projection_flops * n + self.layers * attend * keys
