"""Everything the benchmark takes from the program under test, in one place.

The program is ``src/repro``: its ``ModelConfig``, the parameter layout of
its dense transformer, the paged engine and the weight store.  The engine
has no public accessor of per-request progress, so ``requests`` reads each
request's state (its tokens, logps and prefill progress) without changing
it.
"""
from __future__ import annotations

from typing import Dict, Iterator

import jax
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


def check_layout(params: Dict, mcfg) -> None:
    """Fail early, with both layouts named, if the program's parameter
    layout is not the one ``to_program_params`` builds."""
    from repro.models.api import get_model
    want = jax.eval_shape(lambda: get_model(mcfg).init(jax.random.PRNGKey(0), mcfg))
    got = jax.eval_shape(lambda: params)
    shape = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), t)
    if shape(want) != shape(got):
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{shape(got)} vs {shape(want)}")


def make_task(prompt):
    from repro.data.tasks import MathTask
    return MathTask(prompt="", answer=0, prompt_ids=list(prompt))


def active(engine) -> Iterator:
    """The requests that hold a slot, read only."""
    return iter(list(engine._active.values()))


def finished(engine, since: int = 0) -> list:
    """The requests finished since the ``since``-th, read only."""
    return engine._done[since:]


def prefilling(engine) -> int:
    """Requests that hold a slot and still prefill (or wait to fork)."""
    return sum(r.state in ("PREFILL", "FORK") for r in active(engine))


def queued(engine) -> int:
    return engine.pending - (engine.serve.max_slots - engine.kv.free_slots)
