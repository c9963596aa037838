"""Online health monitoring: detector units on synthetic streams, the
zero-overhead guarantee (monitor off/on bit-identity), and the e2e
injected-straggler scenario where monitor-triggered replanning acts
strictly earlier than the throughput EWMA and wins on throughput."""
import pytest

from repro.core.cluster import paper_heterogeneous
from repro.core.cost_model import LengthDistribution
from repro.core.jobs import TrendConfig
from repro.core.model_spec import PAPER_MODELS
from repro.core.pool import JobSpec, schedule_pool
from repro.core.scheduler import SchedulerConfig, schedule
from repro.core.staleness import StalenessConfig
from repro.obs import (Alert, BurnWindow, HealthMonitor, MetricsRegistry,
                       MonitorConfig, SLOSpec, Tracer, burn_rate,
                       classify_burn)
from repro.sim import (AsyncRLSimulator, ElasticConfig, JobStraggler,
                       MultiJobSimulator, MultiSimConfig, PoolReplanner,
                       SimConfig)

P = LengthDistribution(mean_len=1024, prompt_len=128)
SCHED_CFG = SchedulerConfig(tokens_per_step=2 ** 18, stable_iters=3,
                            max_iters=12, adapt_delta=False)


def _mon(**kw) -> HealthMonitor:
    """Monitor with a short window/poll so unit tests stay compact."""
    base = dict(window_s=30.0, poll_interval_s=2.0, cooldown_s=30.0)
    base.update(kw)
    return HealthMonitor(MonitorConfig(**base))


# ================================================================ detectors
def test_straggler_detector_flags_slow_replica():
    mon = _mon()
    for t in range(10, 30, 2):
        for rep in range(4):
            rate = 20.0 if rep == 0 else 100.0     # r0 is 5× slower
            mon.on_gen_span("j", rep, float(t), 100.0 / rate, 100.0)
    alerts = mon.poll(30.0)
    strag = [a for a in alerts if a.detector == "straggler"]
    assert len(strag) == 1
    a = strag[0]
    assert a.key == "j/r0"
    assert a.severity == "critical"                # z far past 2× threshold
    assert a.evidence["replica"] == 0
    assert a.evidence["job"] == "j"
    assert a.evidence["z"] < -mon.cfg.straggler_z
    assert a.evidence["rate"] < a.evidence["fleet_rate"]
    d = a.to_dict()
    assert d["detector"] == "straggler" and d["evidence"]["replica"] == 0


def test_straggler_detector_quiet_on_healthy_fleet():
    mon = _mon()
    for t in range(0, 30, 2):
        for rep in range(6):
            rate = 100.0 + rep              # mild spread, no outlier
            mon.on_gen_span("j", rep, float(t), 100.0 / rate, 100.0)
    assert mon.poll(30.0) == []


def test_straggler_detector_needs_peers():
    mon = _mon()                            # min_peers=3: 2 replicas can't
    for t in range(0, 30, 2):               # establish a fleet distribution
        mon.on_gen_span("j", 0, float(t), 1.0, 10.0)
        mon.on_gen_span("j", 1, float(t), 1.0, 100.0)
    assert mon.poll(30.0) == []


def test_buffer_detector_gen_ahead_and_train_starved():
    mon = _mon()
    for t in range(0, 20, 2):               # depth pinned at capacity +
        mon.on_buffer("a", float(t), 95, 100)      # capacity stalls
        mon.on_stall("a", float(t), "capacity")
        mon.on_buffer("b", float(t), 2, 100)       # starved + data stalls
        mon.on_stall("b", float(t), "data")
    alerts = mon.poll(20.0)
    modes = {a.evidence["job"]: a.evidence["mode"] for a in alerts
             if a.detector == "buffer"}
    assert modes == {"a": "gen_ahead", "b": "train_starved"}


def test_buffer_detector_quiet_on_balance():
    mon = _mon()
    for t in range(0, 20, 2):
        mon.on_buffer("a", float(t), 50, 100)      # mid depth, no stalls
    assert mon.poll(20.0) == []


def test_staleness_detector_burns_near_eta():
    mon = _mon()
    for i in range(16):                     # everything at η: 100% bad
        mon.on_staleness("j", float(i), 4, eta=4)
    alerts = [a for a in mon.poll(16.0) if a.detector == "staleness"]
    assert len(alerts) == 1
    # objective 0.75 → budget 0.25 → burn 4× on a 100%-bad window
    assert alerts[0].severity == "warn"
    assert alerts[0].evidence["burn"] == pytest.approx(4.0)
    assert alerts[0].evidence["bad_frac"] == 1.0
    mon2 = _mon()
    for i in range(16):                     # all fresh: no burn
        mon2.on_staleness("j", float(i), 0, eta=4)
    assert [a for a in mon2.poll(16.0) if a.detector == "staleness"] == []


def test_bubble_detector_alerts_on_drift():
    mon = _mon(detect_straggler=False, detect_buffer=False,
               detect_staleness=False, detect_admission=False,
               bubble_ref_polls=2, bubble_drift=0.2)
    t = 0.0
    for _ in range(4):                      # dense polls lock a ~0 reference
        for s in range(30):
            mon.on_stage_span("train", t + s, 1.0)
        t += 30.0
        assert mon.poll(t) == []
    for _ in range(3):                      # stage goes 80% idle
        for s in range(0, 30, 5):
            mon.on_stage_span("train", t + s, 1.0)
        t += 30.0
    alerts = mon.poll(t)
    assert any(a.detector == "bubble" and a.key == "train" for a in alerts)


def test_admission_detector_burns_on_slow_admissions():
    mon = _mon()
    for i in range(8):
        mon.on_admission(f"job{i}", float(i), 120.0)   # all above 60s SLO
    alerts = [a for a in mon.poll(8.0) if a.detector == "admission"]
    assert len(alerts) == 1 and alerts[0].key == "pool"
    mon2 = _mon()
    for i in range(8):
        mon2.on_admission(f"job{i}", float(i), 5.0)
    assert [a for a in mon2.poll(8.0) if a.detector == "admission"] == []


def test_cooldown_suppresses_repeat_alerts():
    mon = _mon(cooldown_s=100.0)
    for t in range(10, 30, 2):
        for rep in range(4):
            rate = 20.0 if rep == 0 else 100.0
            mon.on_gen_span("j", rep, float(t), 100.0 / rate, 100.0)
    assert len(mon.poll(30.0)) == 1
    for t in range(30, 40, 2):              # still straggling, inside
        for rep in range(4):                # the cooldown window
            rate = 20.0 if rep == 0 else 100.0
            mon.on_gen_span("j", rep, float(t), 100.0 / rate, 100.0)
    assert mon.poll(40.0) == []
    assert len(mon.alerts) == 1


def test_reset_job_clears_evidence_but_not_cooldown():
    mon = _mon()
    for t in range(10, 30, 2):
        for rep in range(4):
            rate = 20.0 if rep == 0 else 100.0
            mon.on_gen_span("j", rep, float(t), 100.0 / rate, 100.0)
    assert len(mon.poll(30.0)) == 1
    mon.reset_job("j")                      # plan swap: new fleet
    assert mon.poll(32.0) == []             # stale evidence gone


# --------------------------------------------------------------- SLO / burn
def test_burn_window_and_classification():
    slo = SLOSpec("x", objective=0.9, description="")
    bw = BurnWindow(slo, window_s=10.0)
    for t in range(10):
        bw.observe(float(t), bad=(t % 2 == 0))     # 50% bad, budget 10%
    assert bw.n(9.0) == 10
    assert bw.bad_frac(9.0) == pytest.approx(0.5)
    assert bw.burn(9.0) == pytest.approx(5.0)
    assert classify_burn(5.0) == "warn"
    assert classify_burn(15.0) == "critical"
    assert classify_burn(0.5) == ""
    assert burn_rate(0.5, slo) == pytest.approx(5.0)
    bw.observe(25.0, bad=False)             # old samples age out
    assert bw.n(25.0) == 1
    with pytest.raises(ValueError):
        SLOSpec("bad", objective=1.5, description="")


def test_monitor_consumes_registry_snapshots():
    """observe_registry turns staleness histograms + η gauges into the
    same burn-window evidence the direct feeds produce."""
    mx = MetricsRegistry()
    mx.gauge("buffer/eta").set(4)
    h = mx.histogram("buffer/staleness")
    for _ in range(16):
        h.observe(4.0)                      # every rollout at the bound
    mon = _mon(detect_straggler=False, detect_buffer=False,
               detect_bubble=False, detect_admission=False)
    mon.observe_registry(mx, t=10.0)
    alerts = [a for a in mon.poll(12.0) if a.detector == "staleness"]
    assert len(alerts) == 1
    # bucket-resolution estimate: 4.0 lands in (2, 4], frac ≥ 3 of that
    # bucket interpolates to (4−3)/(4−2) = 0.5 — enough to burn 2×
    assert alerts[0].evidence["bad_frac"] == pytest.approx(0.5)
    assert alerts[0].evidence["burn"] >= 1.0


def test_monitor_consumes_trace_stream():
    """A Tracer sink streams replica spans into the straggler detector."""
    tr = Tracer()
    mon = HealthMonitor(MonitorConfig(window_s=30.0, poll_interval_s=2.0),
                        tracer=tr)
    tr.add_sink(mon.on_trace_event)
    for t in range(10, 30, 2):
        for rep in range(4):
            rate = 20.0 if rep == 0 else 100.0
            tr.span("replica", f"j/r{rep}", "generate", float(t),
                    100.0 / rate, tokens=100.0)
    alerts = mon.poll(30.0)
    assert [a.key for a in alerts if a.detector == "straggler"] == ["j/r0"]
    # the alert itself lands back in the trace as an instant event
    assert any(ev[1] == "health" and ev[2] == "straggler"
               and ev[3] == "j/r0"
               for ev in tr._events if ev[0] == "i"), \
        "alert not recorded as a trace instant"


# ========================================================= zero overhead
SIM = dict(n_steps=8, rollouts_per_step=32, eta=4, reward_cost_s=0.1)


@pytest.fixture(scope="module")
def plan():
    return schedule(PAPER_MODELS["1.5B"], paper_heterogeneous(16, 16), P,
                    SCHED_CFG)


def test_single_job_sim_bit_identical_with_monitor(plan):
    off = AsyncRLSimulator(plan, P, SimConfig(**SIM, seed=3)).run()
    mon = HealthMonitor()
    on = AsyncRLSimulator(plan, P, SimConfig(**SIM, seed=3,
                                             monitor=mon)).run()
    assert on.wall_time_s == off.wall_time_s
    assert on.tokens_consumed == off.tokens_consumed
    assert on.rollouts_launched == off.rollouts_launched
    assert on.steps == off.steps
    assert on.mean_staleness == off.mean_staleness
    assert mon.polls > 0                    # the monitor did observe the run


def _pool_and_cluster():
    cluster = paper_heterogeneous(8, 24)
    cfg4 = SchedulerConfig(tokens_per_step=2 ** 18, stable_iters=3,
                           max_iters=12, adapt_delta=False,
                           staleness=StalenessConfig(eta=4))
    cfg2 = SchedulerConfig(tokens_per_step=2 ** 18, stable_iters=3,
                           max_iters=12, adapt_delta=False,
                           staleness=StalenessConfig(eta=2))
    jobs = [JobSpec("j1.5b", PAPER_MODELS["1.5B"], P, cfg4, weight=1.0),
            JobSpec("j7b", PAPER_MODELS["7B"], P, cfg2, weight=4.0)]
    return schedule_pool(jobs, cluster), cluster


@pytest.fixture(scope="module")
def pool_cluster():
    return _pool_and_cluster()


def test_multi_job_sim_bit_identical_with_monitor(pool_cluster):
    pool, _ = pool_cluster
    base = dict(n_steps=6, rollouts_per_step=32, check_invariants=True)
    off = MultiJobSimulator(pool, MultiSimConfig(**base)).run()
    mon = HealthMonitor()
    on = MultiJobSimulator(pool, MultiSimConfig(**base,
                                                monitor=mon)).run()
    assert on.wall_time_s == off.wall_time_s
    assert on.owner_final == off.owner_final
    for n in off.per_job:
        assert on.per_job[n].tokens_consumed == off.per_job[n].tokens_consumed
        assert on.per_job[n].rollouts_launched == \
            off.per_job[n].rollouts_launched
    assert mon.polls > 0


def test_paged_engine_tokens_bit_identical_with_monitor():
    import jax
    from repro.data.tasks import MathTaskGenerator, Tokenizer
    from repro.models.api import ModelConfig, get_model
    from repro.rl.rollout import GenConfig
    from repro.rl.weight_sync import WeightStore
    from repro.serve import PagedEngine, ServeConfig

    tok = Tokenizer()
    tiny = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64,
                       vocab=tok.vocab_size, dtype="float32", remat=False)
    model = get_model(tiny)
    store = WeightStore()
    store.publish(model.init(jax.random.PRNGKey(0), tiny))
    tasks = MathTaskGenerator(seed=0).batch(4)
    gen = GenConfig(max_new_tokens=12)
    sc = ServeConfig(max_slots=4, max_len=96)

    def run(monitor):
        # the engine's spans reach the monitor through a tracer sink
        tracer = None
        if monitor is not None:
            tracer = Tracer()
            tracer.add_sink(monitor.on_trace_event)
        eng = PagedEngine(tiny, store, gen, sc, rng_seed=1, tracer=tracer)
        rollouts, _ = eng.generate(tasks)
        return [r.completion_ids for r in rollouts]

    mon = HealthMonitor()
    assert run(None) == run(mon)
    assert {"decode", "prefill"} <= set(mon._stages)   # spans did land


# ================================================== e2e: monitor beats EWMA
def test_monitor_replan_beats_ewma_on_injected_straggler(pool_cluster):
    """Acceptance (ISSUE 9): three near-dead replicas are injected into
    the heavier job.  The monitor's z-score detector flags them from
    span-rate evidence at launch time; the EWMA only reacts after enough
    slow *train steps* drag its smoothed throughput under threshold.
    Both runs end up excluding the same straggling replica — the monitor
    just gets there strictly earlier, so it spends less wall-clock in
    the degraded regime and wins on end-to-end throughput, with the
    device conservation ledger intact."""
    pool, cluster = pool_cluster
    stragglers = [JobStraggler("j7b", i, factor=0.01, t_start=150.0)
                  for i in (0, 1, 2)]
    base = dict(n_steps=14, rollouts_per_step=256, stragglers=stragglers,
                check_invariants=True)
    # cum_factor 0.01 stays above straggler_threshold=0.005: the builtin
    # threshold trigger stays silent and the EWMA is the only baseline
    # detector in play
    elastic = ElasticConfig(replan_latency_s=4.0, straggler_threshold=0.005)
    trend = TrendConfig(alpha=0.5, min_samples=3, threshold=0.85)

    ewma = MultiJobSimulator(pool, MultiSimConfig(
        **base, replanner=PoolReplanner(cluster, elastic=elastic),
        trend=trend)).run()
    mon = HealthMonitor(MonitorConfig(detect_buffer=False,
                                      detect_bubble=False,
                                      detect_staleness=False))
    mres = MultiJobSimulator(pool, MultiSimConfig(
        **base, replanner=PoolReplanner(cluster, elastic=elastic),
        trend=trend, monitor=mon, monitor_replan=True)).run()

    # EWMA-only: the trend detector did fire (this baseline is live)
    ewma_t = [t.time for t in ewma.replan_triggers if t.reason == "trend"]
    assert ewma_t, "EWMA baseline never triggered — scenario broken"
    # monitor: the straggler alert routed into the replan path...
    mon_t = [t.time for t in mres.replan_triggers
             if t.reason == "monitor_straggler"]
    assert mon_t, "monitor never triggered a replan"
    assert any(a.detector == "straggler" and a.severity == "critical"
               for a in mon.alerts)
    # ...strictly earlier than the EWMA would have
    assert min(mon_t) < min(ewma_t)
    # and the earlier replan wins end-to-end
    assert mres.pool_swaps >= 1 and ewma.pool_swaps >= 1
    w = {"j1.5b": 1.0, "j7b": 4.0}
    assert mres.per_job["j7b"].throughput_tps > \
        ewma.per_job["j7b"].throughput_tps
    assert mres.weighted_throughput(w) > ewma.weighted_throughput(w)
    assert mres.wall_time_s <= ewma.wall_time_s
    # conservation: per-job rollout ledgers and the device ledger
    for res in (ewma, mres):
        for r in res.per_job.values():
            assert r.rollouts_launched == (r.rollouts_trained + r.dropped +
                                           r.rollouts_in_buffer +
                                           r.rollouts_generating)
        assert set(res.owner_final) | res.excluded == \
            {d.index for d in cluster.devices}
        assert not set(res.owner_final) & res.excluded


def test_monitor_off_means_no_replan_interference(pool_cluster):
    """monitor_replan=False: an attached monitor observes and alerts but
    never actuates — sim results match the no-monitor run exactly."""
    pool, cluster = pool_cluster
    stragglers = [JobStraggler("j7b", 0, factor=0.01, t_start=60.0)]
    base = dict(n_steps=6, rollouts_per_step=64, stragglers=stragglers,
                check_invariants=True)
    elastic = ElasticConfig(replan_latency_s=4.0, straggler_threshold=0.005)
    off = MultiJobSimulator(pool, MultiSimConfig(
        **base, replanner=PoolReplanner(cluster, elastic=elastic))).run()
    mon = HealthMonitor()
    on = MultiJobSimulator(pool, MultiSimConfig(
        **base, replanner=PoolReplanner(cluster, elastic=elastic),
        monitor=mon)).run()
    assert on.wall_time_s == off.wall_time_s
    assert [t.time for t in on.replan_triggers] == \
        [t.time for t in off.replan_triggers]
    assert mon.polls > 0                    # it watched, it never steered


def test_monitor_replan_requires_replanner():
    pool, _ = _pool_and_cluster()
    with pytest.raises(ValueError):
        MultiJobSimulator(pool, MultiSimConfig(
            monitor=HealthMonitor(), monitor_replan=True))
