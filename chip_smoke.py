"""Bring-up check: the async GRPO loop on one TPU chip, through its entry points.

    python chip_smoke.py

Runs in one process and spawns none.  Three phases, each a plain function
of a ``ModelConfig`` so the tests can run them at smoke size on the CPU:

  serve   qwen-distill-1.5b at its published widths and depth (28 layers,
          bfloat16, random weights from a seed) answers GRPO groups through
          ``PagedEngine.generate_groups``; each completion's behaviour logps
          must match a teacher-forced ``forward`` over prompt + completion.
  kernel  the same engine rebuilt with ``use_pallas=True``: the paged
          decode-attention kernel must be in the lowered decode step, its
          logps must match the serve phase's, and one training forward with
          the flash-attention kernel must match the one without.
  train   ``AsyncGRPOTrainer(engine="paged")`` at published widths with a
          depth cut takes a few GRPO steps, each publishing its weights.

The vocabulary is the repository's byte tokenizer (259 ids padded to 512),
as in both launchers, not the published 151,936.  Any failed check raises
and the exit code is non-zero.  On success the last line of stdout is one
JSON object naming the device.  Without a TPU the script exits non-zero
before it prints any result.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

SRC = Path(__file__).resolve().parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
import numpy as np                                         # noqa: E402

from repro.configs import get_config                       # noqa: E402
from repro.data.tasks import MathTaskGenerator, Tokenizer  # noqa: E402
from repro.models.api import ModelConfig, get_model        # noqa: E402
from repro.rl.async_trainer import (AsyncGRPOTrainer,      # noqa: E402
                                    TrainerConfig)
from repro.rl.buffer import Rollout                        # noqa: E402
from repro.rl.rollout import GenConfig                     # noqa: E402
from repro.rl.weight_sync import WeightStore               # noqa: E402
from repro.serve import PagedEngine, ServeConfig           # noqa: E402
from repro.serve.model import paged_decode_step            # noqa: E402

ARCH = "qwen-distill-1.5b"
SEED = 0
PROMPTS = 8            # GRPO groups served
GROUP = 4              # completions per prompt (G)
NEW_TOKENS = 128       # every completion runs to this length (no EOS stop)
TRAIN_STEPS = 3
# Deepest whole-layer cut whose train step fits in three quarters of the
# chip's 16 GiB, the rest left for the engine's per-call weight upload and
# the allocator.  From ``compiled.memory_analysis()`` of the un-donated
# train step (B=16, S=160) compiled for a described v5e: 11.30 GB at 12
# layers, 12.23 GB at 13, 13.17 GB at 14; the engine's pool adds 56 MB.
TRAIN_LAYERS = 13

# Tolerances on log-probabilities (nats).  Every path computes in bfloat16
# with float32 softmax and accumulation, but rounds activations at other
# points: paged decode attends one token at a time over the pool, the
# teacher-forced forward over the whole sequence, the kernels in float32
# tiles.  bfloat16 keeps 8 bits, so each rounding of a residual of size ~5
# moves it by up to 2^-6, and the differences grow with depth: on the CPU
# at these widths they average 0.005 nats at 4 layers and 0.008 at 8, at
# most 0.03.  Comparing against positions shifted by one averages 0.22
# nats (0.66 at most), so a wrong position, mask or cache slot fails.
LOGP_MEAN_TOL = 0.05
LOGP_MAX_TOL = 0.3


class SmokeFailure(RuntimeError):
    """A check of the bring-up run failed."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ helpers
def published_config(n_layers: int = 0) -> ModelConfig:
    """The served model at published widths with the byte vocabulary;
    ``n_layers`` > 0 cuts the depth."""
    cfg = get_config(ARCH).replace(vocab=Tokenizer().vocab_size)
    return cfg.replace(n_layers=n_layers) if n_layers else cfg


def _padded_sequences(rollouts: Sequence[Rollout]) -> np.ndarray:
    seqs = [r.prompt_ids + r.completion_ids for r in rollouts]
    toks = np.full((len(seqs), max(map(len, seqs))), Tokenizer.PAD, np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    return toks


def _token_logp(cfg: ModelConfig, params, toks: np.ndarray) -> np.ndarray:
    """Teacher-forced log p(token t | tokens < t) for t ≥ 1: [B, T-1]."""
    model = get_model(cfg)

    @jax.jit
    def f(p, t):
        logits = model.forward(p, cfg, t)[:, :-1, :cfg.vocab]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(lp, t[:, 1:, None], axis=-1)[..., 0]

    return np.asarray(f(params, jnp.asarray(toks)))


def _gap(a: np.ndarray, b: np.ndarray) -> Dict[str, float]:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return {"mean": float(d.mean()), "max": float(d.max()), "n": int(d.size)}


def _require_close(gap: Dict[str, float], what: str) -> None:
    print(f"  {what}: mean |dlogp| {gap['mean']:.5f}  max {gap['max']:.5f}"
          f"  over {gap['n']} tokens  (tol mean {LOGP_MEAN_TOL}, "
          f"max {LOGP_MAX_TOL})", flush=True)
    _require(gap["mean"] <= LOGP_MEAN_TOL and gap["max"] <= LOGP_MAX_TOL,
             f"{what}: logps differ beyond tolerance ({gap})")


def teacher_forced_gap(cfg: ModelConfig, params,
                       rollouts: Sequence[Rollout]) -> Dict[str, float]:
    """The engine's behaviour logps against a full forward pass over
    prompt + completion, at the completion's positions."""
    lp = _token_logp(cfg, params, _padded_sequences(rollouts))
    eng, ref = [], []
    for i, r in enumerate(rollouts):
        p, n = len(r.prompt_ids), len(r.completion_ids)
        eng.append(r.behavior_logp[:n])
        ref.append(lp[i, p - 1:p - 1 + n])
    return _gap(np.concatenate(eng), np.concatenate(ref))


def _generate(cfg: ModelConfig, params, n_prompts: int, group: int,
              new_tokens: int) -> Tuple[PagedEngine, List[Rollout]]:
    """Greedy GRPO groups of ``n_prompts`` seeded prompts through a fresh
    paged engine; every completion runs to ``new_tokens``."""
    store = WeightStore()
    store.publish(params)
    tasks = MathTaskGenerator(seed=SEED).batch(n_prompts)
    max_len = max(len(t.prompt_ids) for t in tasks) + new_tokens
    engine = PagedEngine(
        cfg, store,
        GenConfig(max_new_tokens=new_tokens, greedy=True, eos_id=-1),
        ServeConfig(max_slots=n_prompts * group, max_len=max_len),
        rng_seed=SEED)
    rollouts, _ = engine.generate_groups(tasks, group)
    _require(len(rollouts) == n_prompts * group,
             f"{len(rollouts)} rollouts, expected {n_prompts * group}")
    for r in rollouts:
        _require(len(r.completion_ids) == new_tokens,
                 f"completion of {len(r.completion_ids)} tokens, "
                 f"expected {new_tokens}")
        _require(bool(np.all(np.isfinite(r.behavior_logp))),
                 "non-finite behaviour logp")
    return engine, rollouts


# ------------------------------------------------------------------- phases
def serve_phase(cfg: ModelConfig, *, n_prompts: int = PROMPTS,
                group: int = GROUP, new_tokens: int = NEW_TOKENS):
    """Greedy GRPO groups through the paged engine; returns the weights and
    rollouts the kernel phase compares against."""
    params = get_model(cfg).init(jax.random.PRNGKey(SEED), cfg)
    _, rollouts = _generate(cfg, params, n_prompts, group, new_tokens)
    print(f"  {len(rollouts)} completions of {new_tokens} tokens "
          f"({n_prompts} prompts x G={group}), layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} dtype={cfg.dtype}", flush=True)
    _require_close(teacher_forced_gap(cfg, params, rollouts),
                   "paged decode vs teacher-forced forward")
    return params, rollouts


def kernel_phase(cfg: ModelConfig, params, serve_rollouts: Sequence[Rollout],
                 *, on_chip: bool, n_prompts: int = PROMPTS,
                 group: int = GROUP, new_tokens: int = NEW_TOKENS) -> None:
    """The serve phase again with the Pallas kernels.  ``on_chip`` also
    requires the kernels to be compiled custom calls, so that interpret
    mode cannot pass."""
    kcfg = cfg.replace(use_pallas=True)
    engine, rollouts = _generate(kcfg, params, n_prompts, group, new_tokens)
    if on_chip:
        kv = engine.kv
        slots = jnp.zeros((engine.serve.max_slots,), jnp.int32)
        text = jax.jit(
            lambda p, kp, vp, bt, tok, pos, act:
            paged_decode_step(p, kcfg, kp, vp, bt, tok, pos, act)
        ).lower(params, kv.k_pages, kv.v_pages, jnp.asarray(kv.block_tables),
                slots, slots, slots).as_text()
        _require("tpu_custom_call" in text,
                 "paged decode step lowered without the Pallas kernel")
        print("  tpu_custom_call present in the lowered decode step",
              flush=True)
    _require_close(teacher_forced_gap(cfg, params, rollouts),
                   "kernel decode vs teacher-forced forward")

    # against the serve phase, up to and including each sequence's first
    # differing greedy token: later tokens see different contexts
    a, b = [], []
    for r, s in zip(rollouts, serve_rollouts):
        diff = np.flatnonzero(np.asarray(r.completion_ids)
                              != np.asarray(s.completion_ids))
        k = int(diff[0]) + 1 if diff.size else len(r.completion_ids)
        a.append(r.behavior_logp[:k])
        b.append(s.behavior_logp[:k])
    _require_close(_gap(np.concatenate(a), np.concatenate(b)),
                   "kernel decode vs serve phase, shared prefix")

    toks = _padded_sequences(rollouts)
    if on_chip:
        text = jax.jit(lambda p, t: get_model(kcfg).forward(p, kcfg, t)
                       ).lower(params, jnp.asarray(toks)).as_text()
        _require("tpu_custom_call" in text,
                 "training forward lowered without the flash kernel")
    flash = _token_logp(kcfg, params, toks)
    plain = _token_logp(cfg, params, toks)
    valid = toks[:, 1:] != Tokenizer.PAD
    _require_close(_gap(flash[valid], plain[valid]),
                   "flash-attention forward vs reference forward")


def train_phase(cfg: ModelConfig, *, steps: int = TRAIN_STEPS) -> List[Dict]:
    """A few async GRPO steps with the paged engine, each publishing."""
    tc = TrainerConfig(engine="paged", total_steps=steps, seed=SEED)
    trainer = AsyncGRPOTrainer(cfg, tc)
    before = jax.tree_util.tree_map(np.asarray, trainer.params)
    v0 = trainer.store.version
    history = trainer.run(steps, log_every=1)
    _require(len(history) == steps, f"{len(history)} of {steps} steps ran")
    losses = [m["loss"] for m in history]
    _require(all(np.isfinite(losses)), f"non-finite loss {losses}")
    after = jax.tree_util.tree_map(np.asarray, trainer.params)
    changed = any(not np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)))
    _require(changed, "no parameter changed")
    _require(trainer.store.version == v0 + steps,
             f"weight version {trainer.store.version}, expected {v0 + steps}")
    eta = tc.staleness.eta
    worst = max(m["max_staleness"] for m in history)
    _require(worst <= eta, f"consumed a rollout {worst} versions old > {eta}")
    print(f"  {steps} steps at layers={cfg.n_layers}: loss {losses}, "
          f"weight version {v0} -> {trainer.store.version}, "
          f"max staleness {worst} <= eta={eta}", flush=True)
    return history


# --------------------------------------------------------------------- main
class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def _run_phase(name: str, clock: _CompileClock, fn, *args, **kw):
    print(f"[{name}]", flush=True)
    t0, c0 = time.perf_counter(), clock.seconds
    out = fn(*args, **kw)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{name}] wall {time.perf_counter() - t0:.1f} s, of which "
          f"compile {clock.seconds - c0:.1f} s; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} (process peak so far), "
          f"bytes_in_use {stats.get('bytes_in_use')}", flush=True)
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    import importlib.metadata as md
    import jaxlib
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print(f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={md.version('libtpu')}", flush=True)

    clock = _CompileClock()
    cfg = published_config()
    print(f"model {ARCH}: published widths, {cfg.n_layers} layers; vocab cut "
          f"to the byte tokenizer's {cfg.vocab} ids (padded to "
          f"{cfg.padded_vocab}; published {get_config(ARCH).vocab})",
          flush=True)
    params, rollouts = _run_phase("serve", clock, serve_phase, cfg)
    _run_phase("kernel", clock, kernel_phase, cfg, params, rollouts,
               on_chip=True)
    del params, rollouts
    print(f"train depth cut: {TRAIN_LAYERS} of {cfg.n_layers} layers",
          flush=True)
    _run_phase("train", clock, train_phase, published_config(TRAIN_LAYERS))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
