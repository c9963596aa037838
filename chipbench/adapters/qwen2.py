"""The benchmark's adapter for a Qwen2-type decoder (``chipbench/adapters``):
the program's ``ModelConfig`` and parameter layout for it, and its
operations and bytes.

The counts are the arithmetic of ``repro/core/model_spec.py`` (parameter
counts, ``decode_flops_per_token``, ``train_flops_per_token``,
``kv_bytes_per_token``), copied here so that no later change to the program
moves the yardstick, with attention's term added: a token that attends to
``ctx`` keys costs ``4 * n_heads * head_dim * ctx`` operations a layer
(scores and the weighted sum of values, two per multiply-add).

Configurations are the published ``config.json`` keys of a Qwen2-type
decoder: ``hidden_size``, ``intermediate_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``vocab_size``,
``tie_word_embeddings`` and ``torch_dtype``.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a Qwen2-type ``config.json``."""
    from repro.models.api import ModelConfig
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=int(config["num_hidden_layers"]), d_model=d, n_heads=h,
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        head_dim=int(config.get("head_dim") or d // h),
        qkv_bias=bool(config.get("attention_bias", True)),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config["torch_dtype"])


def to_program_params(w: Dict, mcfg) -> Dict:
    """The reference's weights in the program's layout: stacked layers and
    the vocabulary padded to the program's multiple (padded rows are zero;
    the engine drops their logits before it samples)."""
    pad = mcfg.padded_vocab - mcfg.vocab
    lw = w["layers"]
    layers = {
        "attn_norm": lw["input_norm"],
        "attn": {"wq": lw["q_proj"], "wk": lw["k_proj"], "wv": lw["v_proj"],
                 "wo": lw["o_proj"]},
        "ffn_norm": lw["post_attention_norm"],
        "ffn": {"w_gate": lw["gate_proj"], "w_up": lw["up_proj"],
                "w_down": lw["down_proj"]},
    }
    if mcfg.qkv_bias:
        layers["attn"].update(bq=lw["q_bias"], bk=lw["k_bias"], bv=lw["v_bias"])
    params = {"embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
              "layers": layers, "final_norm": w["final_norm"]}
    if not mcfg.tie_embeddings:
        params["lm_head"] = jnp.pad(w["lm_head"], ((0, 0), (0, pad)))
    return params


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


class Shapes:
    def __init__(self, config: Dict):
        self.d = int(config["hidden_size"])
        self.ff = int(config["intermediate_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = int(config.get("head_dim") or self.d // self.heads)
        self.vocab = int(config["vocab_size"])
        self.tied = bool(config.get("tie_word_embeddings", False))
        self.bias = bool(config.get("attention_bias", True))
        self.dtype_bytes = DTYPE_BYTES[config["torch_dtype"]]

    # ------------------------------------------------------------ params
    @property
    def attn_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * q + 2 * self.d * kv + q * self.d

    @property
    def layer_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        bias = q + 2 * kv if self.bias else 0
        return self.attn_matmul_params + bias + 2 * self.d + 3 * self.d * self.ff

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d

    @property
    def params(self) -> int:
        head = 0 if self.tied else self.vocab * self.d
        return self.layers * self.layer_params + self.embed_params + head + self.d

    @property
    def matmul_params(self) -> int:
        """Parameters that multiply every token: the layers' projections and
        the output head.  The embedding is a lookup, norms and biases are
        elementwise."""
        per_layer = self.attn_matmul_params + 3 * self.d * self.ff
        return self.layers * per_layer + self.vocab * self.d

    def weight_bytes_per_call(self, rows: int) -> int:
        """Least weight bytes one forward call reads, whatever its ``rows``:
        every parameter but the embedding rows it does not look up (taken
        as none)."""
        table = 0 if self.tied else self.embed_params
        return (self.params - table) * self.dtype_bytes

    # ------------------------------------------------------------ KV cache
    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    # ------------------------------------------------------------ FLOPs
    def attn_flops(self, keys: int) -> float:
        """Forward attention operations of one token over ``keys`` keys."""
        return 4.0 * self.layers * self.heads * self.head_dim * keys

    def token_flops(self, keys: int) -> float:
        """Forward operations of one token that attends ``keys`` keys."""
        return 2.0 * self.matmul_params + self.attn_flops(keys)

    def span_flops(self, start: int, n: int) -> float:
        """Forward operations of ``n`` tokens at positions ``start`` ..
        ``start + n - 1``, each attending causally to itself and all before."""
        keys = n * start + n * (n + 1) / 2.0
        return 2.0 * self.matmul_params * n + self.attn_flops(1) * keys

    def train_flops(self, length: int) -> float:
        """Forward and backward operations of one causal sequence of
        ``length`` tokens: three times the forward, nothing recomputed."""
        return 3.0 * self.span_flops(0, length)
