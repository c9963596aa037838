"""End-to-end async GRPO training launcher.

On this CPU container it runs reduced configs for real (examples use it);
on a TPU cluster the same driver runs the full config — the mesh, sharding
rules, checkpointing, and scheduler plan are identical code paths.

    PYTHONPATH=src python -m repro.launch.train --arch qwen-distill-1.5b \
        --smoke --steps 20 --ckpt-dir /tmp/ckpt

Features demonstrated end-to-end: heterogeneity-aware schedule (printed),
async rollout/training with bounded staleness, GRPO updates, versioned
weight sync, atomic checkpoint/restart (resume with the same command).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.obs import log


def main() -> None:
    ap = argparse.ArgumentParser()
    log.add_flags(ap)
    ap.add_argument("--arch", default="qwen-distill-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--prompts-per-step", type=int, default=2)
    ap.add_argument("--eta", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="restore the latest checkpoint and continue "
                         "(from DIR when given, else --ckpt-dir); fails "
                         "loudly when none exists")
    ap.add_argument("--crash-after", type=int, default=0, metavar="N",
                    help="hard-exit (os._exit, no cleanup) after N "
                         "completed steps — crash injection for "
                         "exercising --resume")
    ap.add_argument("--schedule", action="store_true",
                    help="print the AReaL-Hex schedule for the paper's "
                         "heterogeneous cluster before training")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace JSON of the run here "
                         "(view: https://ui.perfetto.dev)")
    ap.add_argument("--metrics", default="",
                    help="write a MetricsRegistry snapshot JSON of the "
                         "run here (inspect: python -m repro.obs analyze "
                         "--metrics PATH)")
    args = ap.parse_args()
    log.configure(args)
    use_compile_cache()

    from repro.configs import get_config, get_smoke_config
    from repro.core.staleness import StalenessConfig
    from repro.data.tasks import Tokenizer
    from repro.optim.adamw import AdamWConfig
    from repro.rl.async_trainer import AsyncGRPOTrainer, TrainerConfig
    from repro.ckpt.checkpoint import CheckpointManager

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tok = Tokenizer()
    cfg = cfg.replace(vocab=tok.vocab_size)

    if args.schedule:
        from repro.core.scheduler import schedule
        from repro.core.cluster import paper_heterogeneous
        plan = schedule(get_config(args.arch).spec, paper_heterogeneous(8, 8))
        log.info("AReaL-Hex schedule (24+24 paper cluster):")
        log.info(plan.describe(), schedule=plan.describe())

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(meta={"launcher": "train", "arch": args.arch})
    registry = None
    if args.metrics:
        from repro.obs import MetricsRegistry
        registry = MetricsRegistry()
    tc = TrainerConfig(
        group_size=args.group_size, prompts_per_step=args.prompts_per_step,
        total_steps=args.steps, seed=args.seed,
        staleness=StalenessConfig(
            eta=args.eta,
            rollouts_per_step=args.group_size * args.prompts_per_step),
        opt=AdamWConfig(lr=args.lr), trace=tracer, metrics=registry)
    trainer = AsyncGRPOTrainer(cfg, tc)

    resume_dir = None
    if args.resume is not None:
        resume_dir = args.resume or args.ckpt_dir
        if not resume_dir:
            ap.error("--resume needs a directory (or --ckpt-dir)")

    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)

    step0 = 0
    restored = None
    if resume_dir is not None:
        from repro.ckpt.checkpoint import restore_checkpoint
        restored = restore_checkpoint(resume_dir)   # raises when empty
    elif mgr:
        restored = mgr.restore_latest()
    if restored:
        step0, state = restored
        trainer.params = jax.tree_util.tree_map(
            lambda a, b: b.astype(a.dtype), trainer.params,
            state["params"])
        trainer.opt_state = state["opt_state"]
        trainer.store.publish(trainer.params)
        trainer.buffer.ctl.version = trainer.store.version
        log.info(f"resumed from step {step0} "
                 f"(weight version {trainer.store.version})",
                 resumed_step=step0,
                 resumed_version=trainer.store.version)

    t0 = time.time()
    done = step0
    while done < args.steps:
        trainer.produce()
        m = trainer.train_one()
        if m is None:
            continue
        done += 1
        if done % tc.publish_every == 0:
            trainer.store.publish(trainer.params)
            trainer.buffer.bump_version()
        if mgr:
            mgr.maybe_save(done, lambda: {
                "params": trainer.params, "opt_state": trainer.opt_state,
                "version": trainer.store.version,
            })
        if args.crash_after and done >= args.crash_after:
            log.info(f"injected crash after step {done}",
                     crash_after=args.crash_after)
            os._exit(17)    # hard kill: no atexit, no flush — a real crash
        if done % 5 == 0 or done == args.steps:
            st = trainer.buffer.stats()
            log.info(f"[{done:4d}/{args.steps}] loss={m['loss']:.4f} "
                     f"reward={trainer.rewarder.stats.mean:.3f} "
                     f"staleness={st['mean_staleness']:.2f} "
                     f"elapsed={time.time()-t0:.0f}s",
                     step=done, steps=args.steps, loss=m["loss"],
                     reward=trainer.rewarder.stats.mean,
                     mean_staleness=st["mean_staleness"],
                     elapsed_s=time.time() - t0)
    if tracer is not None:
        tracer.dump(args.trace)
        log.info(f"trace written to {args.trace} "
                 f"({tracer.n_events} events)", trace=args.trace,
                 events=tracer.n_events)
    if registry is not None:
        registry.to_json(args.metrics)
        log.info(f"metrics written to {args.metrics}",
                 metrics=args.metrics)
    log.info("training complete")


if __name__ == "__main__":
    main()
