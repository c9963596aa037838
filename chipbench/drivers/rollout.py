"""Rollout stage: one replica of the paged engine, in steady state, for a window.

Set-up makes the weights on the device from the seed in one jitted call,
publishes them to the program's ``WeightStore`` (which keeps host copies;
the engine passes them into every jitted call), builds a ``PagedEngine``
with the traffic's pool, and fills its slots with the rollouts in flight
(prompt plus the completion so far), prefilled by the engine's own
``step()``.  That also compiles every program the window uses.  Then the
window: whole ``step()`` calls, with the closed backlog topped up before
each, until ``seconds`` have passed, and the pools are waited for.

End-to-end: ``rollout_tokens_per_s`` (tokens the engine sampled in the
window, over the window's seconds) and ``token_gap_p95_ms`` (95th
percentile over every token but a sequence's first of the time since that
sequence's previous token, with the window's start standing in for tokens
sampled before it).  Both are read from each request's tokens after every
step (``program.active``/``program.finished``, read only).

After the window the engine is freed and the plain reference checks a
sample of the served requests (``harness/check.py``).
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from chipbench.harness import check, common, program
from chipbench.harness.traffic import RolloutPlan


class Progress:
    """Tokens, token gaps and the work done, read from the requests.

    ``shapes`` is the architecture's counts (``chipbench/adapters``).  The
    HBM bytes are kept in two sums: the KV each call reads and writes, and
    the weights, read once per jitted call: each decode step with the slots
    that got a decode token as its rows, each prefill chunk with its
    tokens."""

    def __init__(self, shapes):
        self.shapes = shapes
        self.n: Dict[int, int] = {}          # tokens seen per request
        self.t: Dict[int, float] = {}        # time of its latest token
        self.pf: Dict[int, int] = {}         # prefill progress seen
        self.tokens = 0
        self.gaps: List[float] = []
        self.flops = 0.0
        self.kv_bytes = 0.0
        self.weight_bytes = 0
        self.prefill_calls = 0
        self.done_seen = 0
        self.served: Dict[int, object] = {}  # requests that got a token

    def start(self, engine, t: float) -> None:
        self.done_seen = len(program.finished(engine))
        for r in program.active(engine):
            self.n[r.idx] = len(r.tokens)
            self.pf[r.idx] = r.prefill_done
            if r.tokens:
                self.t[r.idx] = t

    def step(self, engine, t: float) -> None:
        shp, kvb = self.shapes, self.shapes.kv_bytes_per_token
        done = program.finished(engine, self.done_seen)
        self.done_seen += len(done)
        decode_rows = 0
        for r in list(program.active(engine)) + done:
            i, n = r.idx, len(r.tokens)
            a, b = self.pf.get(i, 0), r.prefill_done
            if b < a:                        # preempted and re-admitted
                a = 0
            if b > a:                        # one prefill chunk this step
                self.prefill_calls += 1
                self.flops += shp.span_flops(a, b - a)
                self.kv_bytes += b * kvb     # reads a tokens, writes b - a
                self.weight_bytes += shp.weight_bytes_per_call(b - a)
            self.pf[i] = b
            prev = self.n.get(i, 0)
            if n < prev:
                prev = 0
            if n > prev:
                if prev >= 1:                # decode: one token at prev
                    keys = len(r.prompt) + prev
                    self.flops += shp.token_flops(keys)
                    self.kv_bytes += keys * kvb
                    decode_rows += 1
                if i in self.t:
                    self.gaps.append(t - self.t[i])
                self.t[i] = t
                self.tokens += n - prev
                self.served[i] = r
            self.n[i] = n
        if decode_rows:                      # the step's one decode call
            self.weight_bytes += shp.weight_bytes_per_call(decode_rows)


def _submit(engine, group: List) -> None:
    engine.submit([program.make_task(r.prompt) for r in group],
                  max_new_per_task=[r.max_new for r in group],
                  group_ids=[r.group for r in group])


def run(run: common.Run) -> Dict:
    from repro.rl.rollout import GenConfig
    from repro.rl.weight_sync import WeightStore
    from repro.serve import PagedEngine, ServeConfig

    config, traffic = run.config, run.traffic
    adapter = program.adapter(config)
    reference = program.reference(config)
    mcfg = adapter.model_config(config)
    shapes = adapter.Shapes(config)
    plan = RolloutPlan(traffic, shapes.vocab, run.seed)
    key = common.seed_key(run.seed)

    phases = {}
    mark = lambda name: phases.__setitem__(name, time.perf_counter() - run.t0)
    params = jax.jit(lambda k: adapter.to_program_params(
        reference.init_weights(k, config), mcfg))(key)
    program.check_layout(params, mcfg)
    store = WeightStore()
    store.publish(params)
    del params
    engine = PagedEngine(
        mcfg, store,
        GenConfig(max_new_tokens=plan.max_out, greedy=False,
                  temperature=plan.temperature, top_p=plan.top_p, eos_id=-1),
        ServeConfig(max_slots=plan.max_slots, max_len=plan.max_len,
                    page_size=plan.page, num_pages=plan.num_pages,
                    prefill_chunk=plan.prefill_chunk),
        rng_seed=int(run.seed % (2 ** 31)))
    mark("engine_built")

    # set-up: the in-flight rollouts go through the engine's own loop until
    # every one decodes, a step at a time (so the host never runs ahead of
    # the chip by more than a step), and one more step decodes them all
    inflight = plan.inflight()
    for g in sorted({r.group for r in inflight}):
        _submit(engine, [r for r in inflight if r.group == g])
    pools = lambda: jax.block_until_ready((engine.kv.k_pages, engine.kv.v_pages))
    setup_steps = 0
    while program.queued(engine) or program.prefilling(engine):
        engine.step()
        pools()
        setup_steps += 1
    mark("prefilled")
    engine.step()
    pools()
    mark("warm")
    backlog = plan.backlog()

    def top_up():
        while program.queued(engine) < plan.queue_depth:
            _submit(engine, next(backlog))

    top_up()
    stats0 = dict(vars(engine.stats))
    progress = Progress(shapes)
    compiles0 = run.clock.compiles if run.clock else 0
    setup_compile_s = run.clock.seconds if run.clock else None
    steps = 0
    with common.profiled(run) as box:
        t_start = time.perf_counter()
        progress.start(engine, t_start)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            while True:
                top_up()
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    engine.step()
                steps += 1
                t = time.perf_counter()
                progress.step(engine, t)
                if t - t_start >= run.seconds:
                    break
            jax.block_until_ready((engine.kv.k_pages, engine.kv.v_pages))
        t_end = time.perf_counter()
    window_s = t_end - t_start
    setup_s = t_start - run.t0
    compiles = (run.clock.compiles - compiles0) if run.clock else 0

    st = engine.stats
    d_steps = st.decode_steps - stats0["decode_steps"]
    kept = ((st.decode_slot_steps - stats0["decode_slot_steps"])
            - (st.preempted_slot_steps - stats0["preempted_slot_steps"]))
    counts = {
        "steps": steps, "decode_steps": d_steps,
        "prefill_calls": progress.prefill_calls,
        "prefill_tokens": st.prefill_tokens - stats0["prefill_tokens"],
        "tokens": progress.tokens, "gaps": len(progress.gaps),
        "preemptions": st.preemptions - stats0["preemptions"],
        "admissions": st.admissions - stats0["admissions"],
        "completed": st.completed - stats0["completed"],
        "max_slots": plan.max_slots, "num_pages": plan.num_pages,
        "compile_events_in_window": compiles,
        "setup_s": setup_s, "setup_steps": setup_steps,
        "setup_marks_s": phases,
        "setup_compile_s": setup_compile_s,
    }
    occupancy = kept / (d_steps * plan.max_slots) if d_steps else None

    served = [{"prompt": list(r.prompt), "tokens": list(r.tokens),
               "logps": list(r.logps)} for r in progress.served.values()]
    del engine, store
    gc.collect()
    memory_peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")

    t_check = time.perf_counter()
    rng = np.random.default_rng([run.seed, 2])
    sample = check.sample(served, int(traffic["check"]["requests"]), rng)
    weights = jax.jit(lambda k: reference.init_weights(k, config))(key)
    values = check.readings(reference, weights, config, sample)
    control = (check.readings(reference, weights, config, sample, control=True)
               if run.control else None)
    del weights
    limits = traffic["check"]["limits"]
    correct = check.verdict(values, limits) and progress.tokens > 0
    counts["check_s"] = time.perf_counter() - t_check

    gaps_ms = np.asarray(progress.gaps) * 1e3
    return {
        "correct": bool(correct),
        "attempted": len(progress.served),
        "failed": 0,
        "end_to_end": {
            "rollout_tokens_per_s": progress.tokens / window_s,
            "token_gap_p95_ms": (float(np.percentile(gaps_ms, 95))
                                 if gaps_ms.size else None),
            "setup_s": setup_s,
        },
        "layer": {
            "stage": "rollout", "window_s": window_s, "steps": steps,
            "flops": progress.flops,
            "hbm_bytes": progress.kv_bytes + progress.weight_bytes,
            "hbm_kv_bytes": progress.kv_bytes,
            "hbm_weight_bytes": progress.weight_bytes,
            "slot_occupancy": occupancy, "trace": common.reduce_trace(box),
            "peaks": run.peaks,
        },
        "memory_peak_bytes": memory_peak,
        "checks": {k: (values[k], limits[k]) for k in check.NAMES},
        "counts": {**counts, "checked_requests": len(sample),
                   "checked_tokens": values["served_tokens"]},
        "control": control,
    }
