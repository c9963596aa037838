"""Model protocol: config dataclass + family dispatch + input specs.

``ModelConfig`` is the single source of truth for an architecture; the
scheduler consumes its ``.spec`` (coarse ModelSpec), the launchers consume
``input_specs`` (ShapeDtypeStruct stand-ins for the dry-run), and the RL
substrate consumes the init/forward/decode functions.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.model_spec import ModelSpec
from repro.models.blocks import YaRN


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    # --- MoE
    n_experts: int = 0
    top_k: int = 0
    moe_shard: str = "expert"         # "expert" (EP) | "ffn" (per-expert TP)
    fsdp_params: bool = False         # additionally shard params over the
                                      # data axes (ZeRO-3/FSDP — needed when
                                      # model-axis shards exceed HBM)
    shard_mode: str = "tp"            # "tp" Megatron TP over model axis |
                                      # "dp" pure data parallel (batch over
                                      # BOTH axes, params replicated+ZeRO-3)
    seq_shard: bool = False           # sequence-shard activations over the
                                      # model axis between layers (GSPMD
                                      # sequence parallelism)
    loss_chunk: int = 0               # chunk the unembed+loss over sequence
                                      # (0 = whole-sequence logits)
    cache_shard: str = "hd"           # decode-cache model-axis dim: "hd"
                                      # (head_dim, always divisible) |
                                      # "heads" (kv heads, GSPMD-padded) |
                                      # "ctx" (context dim — flash-decode
                                      # partial softmax, tiny all-reduces)
    moe_group: int = 1024             # GShard routing group size (one-hot
                                      # dispatch volume is linear in it)
    moe_comb_f32: bool = True         # combine weights in f32 (False: bf16)
    moe_fused_combine: bool = False   # contract combine weights inside the
                                      # expert down-projection einsum so the
                                      # TP partial-sum all-reduce lands on
                                      # [tokens, d] instead of [g, E, C, d]
    d_ff_shared: int = 0              # shared-expert SwiGLU width (0: none)
    first_k_dense: int = 0            # leading layers with a dense FFN ...
    d_ff_dense: int = 0               # ... of this width
    norm_topk_prob: bool = True       # renormalize the top-k gates to sum 1
    routed_scaling: float = 1.0       # routed experts' output multiplier
    expert_offset: int = 0            # global id of the first expert held
    experts_held: int = 0             # experts the params hold (0: all);
                                      # the router still spans n_experts
    # --- latent attention (MLA; kv_lora_rank > 0 selects it)
    kv_lora_rank: int = 0             # width of the cached latent c_kv
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0         # roped part of q/k; one shared k_pe
    v_head_dim: int = 0
    rope_scaling: Optional[YaRN] = None
    # --- SSM / hybrid
    ssm_state: int = 0
    attn_window: Optional[int] = None # SWA window; None = full attention
    # --- enc-dec / vlm stub frontends
    n_encoder_layers: int = 0
    encoder_seq: int = 0              # frames (whisper) / patches (internvl)
    encoder_dim: int = 0              # stub embedding dim (0 → d_model)
    # --- details
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    norm_kind: str = "rms"            # "rms" | "layer"
    mlp_kind: str = "swiglu"          # "swiglu" | "gelu"
    vocab_pad_to: int = 256
    dtype: str = "bfloat16"           # params/activations compute dtype
    remat: bool = True                # checkpoint per layer in training fwd
    remat_policy: str = "full"        # "full" | "dots" (save matmul outputs
                                      # — avoids gather-heavy recompute of
                                      # the MoE dispatch chain in backward)
    use_pallas: bool = False          # TPU kernels vs pure-jnp reference path
    unroll_layers: bool = False       # fully unroll layer scans (dry-run: XLA
                                      # cost analysis ignores while-loop trip
                                      # counts, so the roofline lowers unrolled)
    q_chunk: int = 512
    kv_chunk: int = 1024

    # ------------------------------------------------------------- derived
    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab, self.vocab_pad_to)

    @property
    def enc_dim(self) -> int:
        return self.encoder_dim or self.d_model

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def spec(self) -> ModelSpec:
        """Coarse spec for the scheduler's analytic cost models."""
        return ModelSpec(
            name=self.name, family=self.family, n_layers=self.n_layers,
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_ff=self.d_ff, vocab=self.vocab,
            head_dim=self.head_dim, n_experts=self.n_experts,
            top_k=self.top_k, ssm_state=self.ssm_state,
            attn_window=self.attn_window,
            n_encoder_layers=self.n_encoder_layers,
            encoder_seq=self.encoder_seq,
            tie_embeddings=self.tie_embeddings,
            mlp_mats=2 if self.mlp_kind == "gelu" else 3,
            kv_latent_dim=(self.kv_lora_rank + self.qk_rope_head_dim
                           if self.mla else 0),
        )

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def scan_unroll(self) -> int:
        return self.n_layers if self.unroll_layers else 1

    @property
    def sub_quadratic(self) -> bool:
        """True when decode state is O(1) in context (SWA / SSM / hybrid):
        these run the long_500k shape; pure full-attention archs skip it."""
        return self.family in ("ssm", "hybrid") or self.attn_window is not None


# ------------------------------------------------------------------ dispatch
def get_model(cfg: ModelConfig):
    """Return the family module implementing the model protocol."""
    if cfg.family in ("dense", "vlm"):
        from . import transformer
        return transformer
    if cfg.family == "moe":
        from . import moe
        return moe
    if cfg.family == "ssm":
        from . import xlstm
        return xlstm
    if cfg.family == "hybrid":
        from . import hymba
        return hymba
    if cfg.family == "encdec":
        from . import whisper
        return whisper
    raise ValueError(f"unknown family {cfg.family!r}")


# --------------------------------------------------------------- input specs
def train_input_specs(cfg: ModelConfig, *, batch: int, seq_len: int
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for one GRPO train step (no allocation).

    tokens/loss_mask cover the full packed sequence; ``advantages`` are
    per-sequence (GRPO group-normalized), ``behavior_logp`` per token from the
    rollout policy (staleness-decoupled objective).
    """
    f = jnp.dtype(cfg.dtype)
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((batch, seq_len), f),
        "advantages": jax.ShapeDtypeStruct((batch,), jnp.float32),
        "behavior_logp": jax.ShapeDtypeStruct((batch, seq_len), jnp.float32),
    }
    if cfg.family == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq, cfg.enc_dim), f)
    if cfg.family == "vlm":
        specs["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq, cfg.enc_dim), f)
    return specs


def decode_input_specs(cfg: ModelConfig, *, batch: int, ctx_len: int
                       ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Stand-ins for one ``serve_step`` (one new token, KV cache of ctx_len)."""
    specs = {
        "token": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }
    return specs


def cache_specs(cfg: ModelConfig, *, batch: int, ctx_len: int
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct pytree of the decode cache (model-specific)."""
    mod = get_model(cfg)
    return jax.eval_shape(
        lambda: mod.init_cache(cfg, batch=batch, max_len=ctx_len))
