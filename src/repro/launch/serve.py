"""Rollout-serving launcher: batched generation through either engine.

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-1.3b --smoke \
        --batch 8 --max-new 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen-distill-1.5b \
        --smoke --engine paged --batch 16 --slots 4

``--engine`` selects the generation path:

  * ``static`` (default) — the right-padded batch engine
    (``rl.rollout.RolloutEngine``): one prefill, every row decodes until
    the slowest finishes.  Works for every model family.
  * ``paged``  — the continuous-batching engine (``serve.PagedEngine``):
    paged KV cache, per-step admission/eviction, interleaved chunked
    prefill + decode under a token budget.  Dense-transformer families
    only; prints slot/page occupancy and the ``EngineReport`` that feeds
    ``ServingCostModel`` back into the scheduler.  ``--radix`` turns on
    the cross-request radix prefix cache; ``--turns N`` (N > 1, implies
    ``--radix``) drives multi-turn agentic episodes through
    ``rl.agentic.MultiTurnDriver`` with a simulated tool env and prints
    the radix hit rate + env-gap accounting.

Both paths print throughput and a sample completion.  On an equal-length
prompt batch, greedy runs produce token-identical completions across
engines (the fig9 acceptance check); with mixed prompt lengths the
static engine's right-padding shifts its RoPE positions, so completions
legitimately differ between engines (each paged row matches a B=1
static run instead — see tests/test_serve.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.obs import log


def main() -> None:
    ap = argparse.ArgumentParser()
    log.add_flags(ap)
    ap.add_argument("--arch", default="qwen-distill-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("static", "paged"), default="static")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=0,
                    help="paged: concurrent sequences (0 → batch size)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged: tokens per KV page (0 → tuned default)")
    ap.add_argument("--radix", action="store_true",
                    help="paged: cross-request radix prefix cache")
    ap.add_argument("--turns", type=int, default=1,
                    help="paged: multi-turn episodes via a simulated "
                         "tool env (turns > 1 implies --radix)")
    ap.add_argument("--tool-tokens", type=int, default=12,
                    help="paged: observation tokens injected per turn")
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics", default="",
                    help="write a MetricsRegistry snapshot JSON of the "
                         "serve run here (inspect: python -m repro.obs analyze "
                         "--metrics PATH)")
    args = ap.parse_args()
    log.configure(args)
    use_compile_cache()

    from repro.configs import get_config, get_smoke_config
    from repro.data.tasks import MathTaskGenerator, Tokenizer
    from repro.models.api import get_model
    from repro.rl.rollout import GenConfig, RolloutEngine
    from repro.rl.weight_sync import WeightStore

    tok = Tokenizer()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(vocab=tok.vocab_size)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed), cfg)
    store = WeightStore()
    store.publish(params)
    gen_cfg = GenConfig(max_new_tokens=args.max_new, greedy=args.greedy)
    gen = MathTaskGenerator(seed=args.seed)
    tasks = gen.batch(args.batch)

    multi_turn = args.engine == "paged" and args.turns > 1
    if args.engine == "paged":
        from repro.serve import EngineReport, PagedEngine, ServeConfig
        slots = args.slots or args.batch
        plen = max(len(t.prompt_ids) for t in tasks)
        extra = (args.turns - 1) * (args.max_new + args.tool_tokens)
        engine = PagedEngine(
            cfg, store, gen_cfg,
            ServeConfig(max_slots=slots,
                        max_len=plen + args.max_new + extra,
                        page_size=args.page_size or None,
                        radix=args.radix or multi_turn),
            rng_seed=args.seed)
    else:
        engine = RolloutEngine(cfg, store, gen_cfg, rng_seed=args.seed)

    t0 = time.time()
    if multi_turn:
        from repro.rl.agentic import EnvConfig, MultiTurnDriver, SimToolEnv
        drv = MultiTurnDriver(engine, SimToolEnv(EnvConfig(
            turns=args.turns, tool_tokens=args.tool_tokens,
            seed=args.seed)))
        episodes, metrics = drv.run(tasks, greedy=args.greedy)
        rollouts = [e.final for e in episodes]
        metrics["mean_len"] = float(np.mean(
            [len(r.completion_ids) for r in rollouts]))
        metrics["slot_occupancy"] = engine.stats.slot_occupancy
        metrics["page_occupancy"] = engine.stats.page_occupancy
        log.info(f"multi-turn: turns={metrics['turns']} "
                 f"env_calls={metrics['env_calls']} "
                 f"env_wait_s={metrics['env_wait_s']:.3f}  "
                 f"radix_hit_rate={metrics['radix_hit_rate']:.2f}",
                 turns=metrics["turns"], env_calls=metrics["env_calls"],
                 env_wait_s=metrics["env_wait_s"],
                 radix_hit_rate=metrics["radix_hit_rate"])
    else:
        rollouts, metrics = engine.generate(tasks)
    dt = time.time() - t0
    n_tok = sum(len(r.completion_ids) for r in rollouts)
    log.info(f"[{args.engine}] generated {n_tok} tokens for {args.batch} "
             f"requests in {dt:.2f}s  ({n_tok/dt:.1f} tok/s)  "
             f"mean_len={metrics['mean_len']:.1f}  "
             f"decode_slot_steps={metrics.get('decode_slot_steps', '?')}",
             engine=args.engine, tokens=n_tok, batch=args.batch,
             seconds=dt, tok_per_s=n_tok / dt,
             mean_len=metrics["mean_len"],
             decode_slot_steps=metrics.get("decode_slot_steps"))
    if args.engine == "paged":
        log.info(f"slot_occupancy={metrics['slot_occupancy']:.2f}  "
                 f"page_occupancy={metrics['page_occupancy']:.2f}  "
                 f"preemptions={metrics['preemptions']}",
                 slot_occupancy=metrics["slot_occupancy"],
                 page_occupancy=metrics["page_occupancy"],
                 preemptions=metrics["preemptions"])
        from repro.kernels import tuning
        # ServingCostModel keys reports by DeviceProfile name; fall back to
        # the raw device kind (unpriceable, but still human-readable) when
        # the local accelerator maps to no profile (e.g. CPU smoke runs)
        dev = (tuning.current_device_type()
               or jax.devices()[0].device_kind)
        report = EngineReport.from_stats(
            engine.stats, dev, engine="paged",
            tokens_per_sec=n_tok / dt,
            turns_per_episode=float(metrics.get("turns", 1)),
            turn_gap_s=float(metrics.get("turn_gap_s", 0.0)))
        log.info(f"engine report: {report}", report=report)
    if args.metrics:
        from repro.obs import MetricsRegistry
        registry = MetricsRegistry()
        registry.counter("serve/tokens").inc(n_tok)
        registry.counter("serve/requests").inc(args.batch)
        registry.gauge("serve/tok_per_s").set(n_tok / dt)
        registry.gauge("serve/mean_len").set(float(metrics["mean_len"]))
        lat_hist = registry.histogram("serve/completion_len")
        for ro in rollouts:
            lat_hist.observe(float(len(ro.completion_ids)))
        if args.engine == "paged":
            registry.gauge("serve/slot_occupancy").set(
                float(metrics["slot_occupancy"]))
            registry.gauge("serve/page_occupancy").set(
                float(metrics["page_occupancy"]))
            registry.counter("serve/preemptions").inc(
                int(metrics.get("preemptions", 0)))
        registry.to_json(args.metrics)
        log.info(f"metrics written to {args.metrics}",
                 metrics=args.metrics)
    r = rollouts[0]
    log.info(f"sample prompt:     {tok.decode(r.prompt_ids)!r}",
             prompt=tok.decode(r.prompt_ids))
    log.info(f"sample completion: {tok.decode(r.completion_ids)!r}",
             completion=tok.decode(r.completion_ids))


if __name__ == "__main__":
    main()
