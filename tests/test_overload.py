"""SLO-aware overload control tests: token-bucket admission + bounded-queue
backpressure (REJECTED accounting, retry-after hints, bit-identity when the
limits never bind), the brownout degradation ladder (monotone single-step
moves, hysteresis, oracle bit-equality at a forced level), the crash-storm
circuit breaker (unit transitions + retry-storm A/B on a scripted burst),
jittered crash backoff determinism, deadline sweeps over parked requests
and the disagg handoff queue, and the SLO feedback paths into the split
policy and the fair-share allocator."""
import numpy as np
import pytest
from jax import set_mesh

from repro.cluster import FairShareAllocator, JobDemand
from repro.configs import get_config, smoke_variant
from repro.faults import FaultInjector, FaultPlan, crash_storm, worker_crash
from repro.obs import SLOTracker, Tracer, meets_slo, overload_timeline
from repro.serve import (AdmissionController, CircuitBreaker,
                         DegradationLadder, DisaggEngine, QueueSplitPolicy,
                         Request, RequestState, ServeEngine, SplitObs,
                         TokenBucket, synthetic_requests)


@pytest.fixture(scope="module")
def cfg():
    return smoke_variant(get_config("smollm-360m"))


KW = dict(capacity=4, cache_len=32, prefill_bucket=8, seed=0)


def _burst(cfg, n=8, seed=0, prompt=(6, 16), max_new=(5, 9), **kw):
    return synthetic_requests(n, vocab_size=cfg.vocab_size,
                              arrivals=np.zeros(n), prompt_len=prompt,
                              max_new_tokens=max_new,
                              rng=np.random.default_rng(seed), **kw)


def _streams(metrics, *, finished_only=True):
    return {r.rid: tuple(r.generated) for r in metrics.requests
            if not finished_only or r.state is RequestState.FINISHED}


def _drive(eng, reqs, *, max_ticks=500):
    """Tick-clock drive: 1 tick = 1 simulated second (deterministic TTFT/
    TPOT for SLO assertions; engines built with clock=... can't use run())."""
    eng.submit(reqs)
    with set_mesh(eng.mesh):
        while (eng.scheduler.has_pending or eng._by_slot or eng._prefilling
               or eng._retrying) and eng._tick < max_ticks:
            eng._clk = float(eng._tick)
            eng.tick()
    eng.metrics.wall_s = float(eng._tick)
    return eng.metrics


def _tick_engine(cfg, **kw):
    """ServeEngine on an injected tick clock (see _drive)."""
    holder = {}
    eng = ServeEngine(cfg, clock=lambda: holder["e"]._clk, **kw)
    eng._clk = 0.0
    holder["e"] = eng
    return eng


# ---------------------------------------------------------------------------
# Token bucket + admission controller (host-only units)
# ---------------------------------------------------------------------------


def test_token_bucket_refill_property():
    """Seeded fuzz: over any arrival sequence, tokens stay in [0, burst]
    and the number of admits can never exceed burst + rate * elapsed."""
    rng = np.random.default_rng(7)
    for trial in range(20):
        rate = float(rng.uniform(0.5, 8.0))
        burst = int(rng.integers(1, 6))
        b = TokenBucket(rate, burst)
        now, admits = 0.0, 0
        for _ in range(200):
            now += float(rng.exponential(0.3))
            if b.try_take(now):
                admits += 1
            assert 0.0 <= b.tokens <= burst + 1e-9
        assert admits <= burst + rate * now + 1e-6


def test_token_bucket_deterministic_and_clamped():
    b1, b2 = TokenBucket(2.0, 2), TokenBucket(2.0, 2)
    seq = [0.0, 0.1, 0.5, 0.4, 2.0]  # includes a non-monotonic step
    assert [b1.try_take(t) for t in seq] == [b2.try_take(t) for t in seq]
    b = TokenBucket(1.0, 1)
    assert b.try_take(10.0)
    b._refill(0.0)  # time going backwards must not mint tokens
    assert b.tokens < 1.0
    with pytest.raises(ValueError):
        TokenBucket(0.0, 1)
    with pytest.raises(ValueError):
        TokenBucket(1.0, 0)


def test_admission_controller_reasons_and_hints():
    ac = AdmissionController(tenant_rate=1.0, queue_cap=3)
    full = ac.check("a", 0.0, 3)
    assert full is not None and full.reason == "queue_full"
    assert full.retry_after > 0
    assert ac.check("a", 0.0, 0) is None  # burst token
    rated = ac.check("a", 0.0, 0)
    assert rated is not None and rated.reason == "rate"
    assert rated.retry_after > 0
    assert ac.rejected_queue == 1 and ac.rejected_rate == 1
    # per-tenant dict rates: an unlisted tenant is not rate-limited
    ac2 = AdmissionController(tenant_rate={"a": 1.0})
    assert ac2.check("b", 0.0, 10) is None
    disabled = AdmissionController()
    assert not disabled.enabled


# ---------------------------------------------------------------------------
# Bounded queue + rejection accounting (engine)
# ---------------------------------------------------------------------------


def test_bounded_queue_cap_and_accounting(cfg):
    """The admission queue never exceeds its cap; every offered request is
    exactly one of finished/rejected; rejects carry a retry-after hint."""
    eng = _tick_engine(cfg, kv_layout="paged", n_workers=1, queue_cap=3,
                       debug_checks=True, **KW)
    reqs = _burst(cfg, n=10)
    eng.submit(reqs)
    assert eng.scheduler.queue_len() <= 3
    with set_mesh(eng.mesh):
        while (eng.scheduler.has_pending or eng._by_slot or eng._prefilling
               or eng._retrying) and eng._tick < 500:
            eng._clk = float(eng._tick)
            eng.tick()
            assert eng.scheduler.queue_len() <= 3
    states = [r.state for r in reqs]
    fin = sum(1 for s in states if s is RequestState.FINISHED)
    rej = sum(1 for s in states if s is RequestState.REJECTED)
    assert fin + rej == len(reqs) and rej > 0
    for r in reqs:
        if r.state is RequestState.REJECTED:
            assert r.retry_after is not None and r.retry_after > 0
            assert not r.generated  # rejected before any compute
    s = eng.metrics.summarize()
    assert s["rejected_requests"] == rej
    assert s["shed_requests"] == 0  # backpressure, not shedding


def test_bit_identity_when_limits_never_bind(cfg):
    """Generous limits + SLO tracking must be bit-identical to a
    no-control engine: flat, paged, and disagg."""
    loose = dict(tenant_rate=1000.0, queue_cap=1000,
                 slo_ttft=1e9, slo_tpot=1e9)
    for layout in ("flat", "paged"):
        want = _streams(ServeEngine(cfg, kv_layout=layout, n_workers=1,
                                    **KW).run(_burst(cfg)))
        m = ServeEngine(cfg, kv_layout=layout, n_workers=1, **loose,
                        **KW).run(_burst(cfg))
        assert _streams(m) == want
        assert sum(1 for r in m.requests
                   if r.state is RequestState.REJECTED) == 0
    want = _streams(DisaggEngine(cfg, n_workers=2, debug_checks=True,
                                 **KW).run(_burst(cfg)))
    md = DisaggEngine(cfg, n_workers=2, debug_checks=True, **loose,
                      **KW).run(_burst(cfg))
    assert _streams(md) == want
    assert md.summarize()["rejected_requests"] == 0


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------


def test_ladder_monotone_single_steps_and_hysteresis():
    lad = DegradationLadder(up_patience=2, down_patience=3)
    hot = lambda: lad.update(0.5, 20, 4)   # noqa: E731
    cool = lambda: lad.update(1.0, 0, 4)   # noqa: E731
    hold = lambda: lad.update(0.95, 4, 4)  # noqa: E731  dead band
    levels = [hot() for _ in range(20)]
    # at most one step per update, and never above max_level
    assert all(b - a <= 1 for a, b in zip(levels, levels[1:]))
    assert levels[-1] == 5 == lad.max_level
    # dead band holds the level indefinitely (no flapping)
    assert [hold() for _ in range(10)] == [5] * 10
    # de-escalation needs down_patience consecutive cool ticks
    assert cool() == 5 and cool() == 5 and cool() == 4
    # a single hot tick resets the cool streak (hysteresis)
    assert cool() == 4 and cool() == 4 and hot() == 4
    assert [cool() for _ in range(3)] == [4, 4, 3]
    # full recovery reaches normal
    for _ in range(30):
        cool()
    assert lad.level == 0 and lad.name == "normal"


def test_ladder_up_patience_gates_escalation():
    lad = DegradationLadder(up_patience=3, down_patience=1)
    assert lad.update(0.0, 99, 4) == 0
    assert lad.update(0.0, 99, 4) == 0
    assert lad.update(0.0, 99, 4) == 1  # third consecutive hot tick


def test_brownout_engine_degrades_and_recovers(cfg):
    """Under a burst the auto ladder escalates (traced, recorded); streams
    of finished requests stay bit-equal to the unthrottled oracle (levels
    1-3 trade latency, never content)."""
    want = _streams(ServeEngine(cfg, kv_layout="paged", n_workers=1,
                                spec="ngram", spec_k=4, **KW)
                    .run(_burst(cfg, n=12)))
    tracer = Tracer(name="brownout-test")
    # ladder capped below park/shed so every finished stream must match
    eng = _tick_engine(cfg, kv_layout="paged", n_workers=1, spec="ngram",
                       spec_k=4, brownout="auto",
                       ladder=DegradationLadder(up_patience=1,
                                                down_patience=2,
                                                max_level=3),
                       slo_ttft=2.0, slo_tpot=1.0, tracer=tracer, **KW)
    m = _drive(eng, _burst(cfg, n=12))
    s = m.summarize()
    assert s["brownout_level_max"] >= 1
    assert s["brownout_events"], "transitions must be recorded"
    assert _streams(m) == want
    names = {e.name for e in tracer.events if e.track == "overload"}
    assert "degrade.enter" in names
    # transitions are (tick, level, label) and strictly ordered
    ticks = [t for t, _, _ in s["brownout_events"]]
    assert ticks == sorted(ticks)


def test_brownout_forced_level_bit_equal_to_static_oracle(cfg):
    """Degraded-mode invariant: at a pinned ladder level the engine is
    bit-equal to an oracle statically configured the same way (level 3 =
    spec off + chunk width capped at one page)."""

    class Pinned(DegradationLadder):
        def update(self, attainment, queue_depth, capacity):
            self.level = 3
            return 3

    eng = _tick_engine(cfg, kv_layout="paged", n_workers=1, spec="ngram",
                       spec_k=4, brownout="auto", ladder=Pinned(),
                       chunked_prefill=True, prefill_chunk=16, page_size=8,
                       debug_checks=True, **KW)
    got = _streams(_drive(eng, _burst(cfg)))
    oracle = ServeEngine(cfg, kv_layout="paged", n_workers=1,
                         chunked_prefill=True, prefill_chunk=8, page_size=8,
                         **KW).run(_burst(cfg))
    assert got == _streams(oracle)
    assert eng.spec_k == 0 and eng.drafter is None
    assert eng.prefill_chunk == 8


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_unit_transitions():
    br = CircuitBreaker(threshold=3, window=4, cooldown=2, probe_ticks=2,
                        probe_admits=1)
    assert br.update(0, 1) is None and br.state == "closed"
    assert br.update(1, 1) is None
    assert br.update(2, 1) == "open" and br.admit_limit() == 0
    assert br.update(3, 0) is None  # cooling down
    assert br.update(4, 0) == "half_open" and br.admit_limit() == 1
    # a fault during the probe re-opens
    assert br.update(5, 1) == "open"
    assert br.update(7, 0) == "half_open"
    assert br.update(8, 0) is None
    assert br.update(9, 0) == "closed" and br.admit_limit() is None
    # window cleared on close: one old fault doesn't instantly re-open
    assert br.update(10, 1) is None and br.state == "closed"


def test_breaker_window_expires_old_faults():
    br = CircuitBreaker(threshold=2, window=2)
    assert br.update(0, 1) is None
    assert br.update(5, 1) is None, "faults outside the window must expire"
    assert br.state == "closed"


def test_breaker_prevents_retry_storm(cfg):
    """Scripted 3-crash storm on the same worker: with the breaker armed,
    retry re-executions drop (victims + fresh admissions stop feeding the
    next crash) and recovery does not regress; every request still
    finishes, bit-equally."""

    def run(with_breaker):
        inj = FaultInjector(FaultPlan(crash_storm(2, 3, 3, worker=0)))
        br = (CircuitBreaker(threshold=2, window=8, cooldown=5,
                             probe_ticks=2) if with_breaker else None)
        eng = ServeEngine(cfg, kv_layout="paged", n_workers=4, capacity=4,
                          cache_len=32, prefill_bucket=8, seed=0,
                          slots_per_chunk=1, retry_jitter=False,
                          fault_injector=inj, breaker=br)
        m = eng.run(_burst(cfg, n=16, max_new=(8, 12)))
        return m.summarize(), _streams(m)

    plain, streams_plain = run(False)
    braked, streams_braked = run(True)
    assert streams_plain == streams_braked
    assert braked["requests_finished"] == plain["requests_finished"] == 16
    assert plain["shed_requests"] == braked["shed_requests"] == 0
    assert braked["retries_total"] < plain["retries_total"]
    assert braked["recovery_ticks_mean"] <= plain["recovery_ticks_mean"]
    kinds = [k for _, k in braked["breaker_events"]]
    assert kinds[0] == "open" and "half_open" in kinds
    assert braked["breaker_events"][-1][1] == "closed"


def test_crash_storm_helper_validates():
    evs = crash_storm(4, n=3, every=2, worker=1, pool="decode")
    assert [(e.at, e.target, e.payload.get("pool")) for e in evs] == \
        [(4, 1, "decode"), (6, 1, "decode"), (8, 1, "decode")]
    with pytest.raises(ValueError):
        crash_storm(0, n=0)
    with pytest.raises(ValueError):
        crash_storm(0, every=0)


# ---------------------------------------------------------------------------
# Jittered retry backoff
# ---------------------------------------------------------------------------


def test_jittered_backoff_deterministic_and_desynchronized(cfg):
    """Jitter draws from the engine RNG: deterministic per seed, and a
    multi-victim crash spreads re-admissions over distinct ticks."""

    def backoffs(seed):
        eng = ServeEngine(cfg, kv_layout="paged", n_workers=1,
                          **{**KW, "seed": seed})
        return [eng._backoff_ticks(3) for _ in range(8)]

    assert backoffs(0) == backoffs(0)
    assert backoffs(0) != backoffs(1)
    eng = ServeEngine(cfg, kv_layout="paged", n_workers=1, **KW)
    draws = {eng._backoff_ticks(3) for _ in range(16)}
    base = eng.retry_backoff * 4
    assert all(1 <= d <= int(base * 1.5) + 1 for d in draws)
    assert len(draws) > 1, "jitter must desynchronize a victim cohort"
    eng.retry_jitter = False
    assert eng._backoff_ticks(3) == base


# ---------------------------------------------------------------------------
# Deadline sweeps: parked requests and the disagg handoff queue
# ---------------------------------------------------------------------------


def test_parked_past_deadline_is_shed_and_pages_freed(cfg):
    """A PARKED request whose deadline passes while its KV sits on host is
    shed at the next tick and its parked payload freed (no page leak)."""
    eng = _tick_engine(cfg, kv_layout="paged", n_workers=1, evict=True,
                       debug_checks=True, **KW)
    reqs = _burst(cfg, n=4, max_new=(8, 10))
    eng.submit(reqs)
    with set_mesh(eng.mesh):
        while not eng._by_slot and eng._tick < 50:
            eng._clk = float(eng._tick)
            eng.tick()
        victim = next(iter(eng._by_slot.values()))
        eng.park_excess(1)
        assert victim.state is RequestState.PARKED
        assert eng.mem.n_parked == 1
        victim.deadline = 1e-9  # already blown relative to arrival 0
        eng._clk = float(eng._tick)
        eng.tick()
        assert victim.state is RequestState.EXPIRED
        assert eng.mem.n_parked == 0
        while (eng.scheduler.has_pending or eng._by_slot or eng._prefilling
               or eng._retrying) and eng._tick < 500:
            eng._clk = float(eng._tick)
            eng.tick()
    assert all(r.state is RequestState.FINISHED
               for r in reqs if r is not victim)


def test_disagg_handoff_deadline_sweep(cfg):
    """A request whose deadline blows while parked BETWEEN the pools is
    swept from the handoff queue (neither half's scheduler sees it there);
    the payload is dropped, nothing leaks, and the decode pool never
    adopts the doomed pages."""
    reqs = _burst(cfg, n=4)
    for r in reqs:
        r.deadline = 1e-9
    d = DisaggEngine(cfg, n_workers=2, debug_checks=True, **KW)
    m = d.run(reqs)
    assert all(r.state is RequestState.EXPIRED for r in m.requests)
    assert d.prefill.mem.n_parked == 0 and d.decode.mem.n_parked == 0
    assert m.summarize()["shed_requests"] == 4
    # and a mixed run: only the doomed request is swept
    reqs2 = _burst(cfg, n=4, seed=1)
    reqs2[2].deadline = 1e-9
    d2 = DisaggEngine(cfg, n_workers=2, debug_checks=True, **KW)
    m2 = d2.run(reqs2)
    states = {r.rid: r.state for r in m2.requests}
    assert states[reqs2[2].rid] is RequestState.EXPIRED
    assert sum(1 for s in states.values()
               if s is RequestState.FINISHED) == 3


# ---------------------------------------------------------------------------
# SLO tracker + feedback into split policy and allocator
# ---------------------------------------------------------------------------


def test_slo_tracker_windows_and_tenants():
    t = SLOTracker(ttft_target=1.0, tpot_target=0.5, window=4)
    assert t.attainment() is None  # empty window
    for ttft in (0.5, 0.5, 2.0, 0.5):
        t.observe(ttft=ttft, tpot=0.1)
    assert t.attainment() == 0.75
    assert t.ttft_attainment() == 0.75 and t.tpot_attainment() == 1.0
    for _ in range(4):  # window slides: old miss forgotten
        t.observe(ttft=0.5, tpot=0.1)
    assert t.attainment() == 1.0
    t.observe(tenant="vip", ttft=9.0, tpot=0.1)
    assert t.tenant_attainment("vip") == 0.0
    # per-request override beats the default target
    assert t.observe(ttft=5.0, tpot=0.1, ttft_target=10.0)
    assert meets_slo(0.5, None, 1.0, 0.5)  # tpot exempt until measurable
    assert not meets_slo(2.0, 0.1, 1.0, 0.5)


def test_slo_tracker_traces_misses():
    tracer = Tracer(name="slo-test")
    t = SLOTracker(ttft_target=1.0, tracer=tracer)
    t.observe(rid=7, ttft=5.0)
    tl = overload_timeline(tracer)
    assert tl["counts"].get("slo.miss") == 1
    assert tl["timeline"][0][2]["rid"] == 7


def test_split_policy_slo_mode():
    obs = lambda ttft, tpot: SplitObs(  # noqa: E731
        total_workers=4, prefill_backlog_tokens=50,
        decode_backlog_tokens=50, prefill_tick_s=0.0, decode_tick_s=0.0,
        handoff_depth=0, tick=4, ttft_attainment=ttft,
        tpot_attainment=tpot)
    pol = QueueSplitPolicy(interval=4, mode="slo", slo_deadband=0.05)
    assert pol.decide(obs(0.5, 0.9), current=2) == 3  # TTFT hurting
    assert pol.decide(obs(0.9, 0.5), current=2) == 1  # TPOT hurting
    assert pol.decide(obs(0.9, 0.88), current=2) == 2  # dead band holds
    assert pol.decide(obs(0.0, 1.0), current=3) == 3  # clamped at hi
    # attainment unknown -> falls back to the backlog rule
    cold = SplitObs(total_workers=4, prefill_backlog_tokens=300,
                    decode_backlog_tokens=0, prefill_tick_s=0.0,
                    decode_tick_s=0.0, handoff_depth=0, tick=4)
    assert pol.decide(cold, current=2) == 3
    with pytest.raises(ValueError):
        QueueSplitPolicy(mode="nope")


def test_allocator_slo_boost():
    alloc = FairShareAllocator(slo_boost=2.0)
    base = JobDemand("j", 4)
    assert alloc.effective_weight(base) == 1.0  # attainment None: no tilt
    meeting = JobDemand("j", 4, attainment=1.0)
    missing = JobDemand("j", 4, attainment=0.0)
    assert alloc.effective_weight(meeting) == 1.0
    assert alloc.effective_weight(missing) == 2.0
    halfway = JobDemand("j", 4, attainment=0.5)
    assert alloc.effective_weight(halfway) == pytest.approx(1.5)
    # out-of-range attainment is clamped, never inverts the boost
    assert alloc.effective_weight(
        JobDemand("j", 4, attainment=7.0)) == 1.0
    # the boost shifts real allocations toward the missing job
    out = alloc.allocate(8, [JobDemand("miss", 8, attainment=0.0),
                             JobDemand("meet", 8, attainment=1.0)])
    assert out["miss"] > out["meet"]
    with pytest.raises(ValueError):
        FairShareAllocator(slo_boost=0.5)


def test_scheduler_allow_bypass_skips_paused_heads(cfg):
    """The `allow` filter admits the first MATCHING request per tenant
    queue, not just the head: a paused fresh head must not head-of-line
    block a crash victim queued behind it (recovery bypass)."""
    from repro.serve.scheduler import SlotScheduler
    fresh, victim = _burst(cfg, n=2, max_new=(4, 5))
    victim.retries = 1
    victim.arrival_time = fresh.arrival_time + 0.25  # behind the head
    sched = SlotScheduler(4, n_workers=1)
    sched.submit(fresh)
    sched.submit(victim)
    got = sched.admit(1.0, allow=lambda r: r.retries > 0)
    assert got == [victim]
    assert sched.pending == [fresh]  # fresh head untouched, still FCFS
    # no filter: plain FCFS order is unchanged by the bypass machinery
    sched2 = SlotScheduler(4, n_workers=1)
    f2, v2 = _burst(cfg, n=2, max_new=(4, 5))
    v2.retries, v2.arrival_time = 1, f2.arrival_time + 0.25
    sched2.submit(f2)
    sched2.submit(v2)
    assert sched2.admit(1.0) == [f2, v2]


def test_breaker_open_holds_retries_then_drains(cfg):
    """An OPEN breaker holds crash victims in backoff (no requeue — they
    must not feed the next crash) and pauses fresh admission; at
    half-open the probe window re-admits them and the run completes."""
    eng = _tick_engine(cfg, kv_layout="paged", n_workers=2,
                       breaker=CircuitBreaker(threshold=1, window=4,
                                              cooldown=4, probe_ticks=2),
                       **KW)
    reqs = _burst(cfg, n=6, max_new=(6, 8))
    eng.submit(reqs)
    with set_mesh(eng.mesh):
        while not eng._by_slot and eng._tick < 50:
            eng._clk = float(eng._tick)
            eng.tick()
        eng.crash_worker()
        victims = [r for r in reqs if r.retries > 0]
        assert victims
        eng._clk = float(eng._tick)
        eng.tick()  # breaker sees the fault and opens
        assert eng.breaker.state == "open"
        held = len(eng._retrying)
        assert held == len(victims)
        q_open = eng.scheduler.queue_len()
        for _ in range(2):  # still open: nothing moves
            eng._clk = float(eng._tick)
            eng.tick()
            if eng.breaker.state != "open":
                break
            assert len(eng._retrying) == held
            assert eng.scheduler.queue_len() == q_open
        while (eng.scheduler.has_pending or eng._by_slot or eng._prefilling
               or eng._retrying) and eng._tick < 500:
            eng._clk = float(eng._tick)
            eng.tick()
    assert eng.breaker.state == "closed"
    assert all(r.state is RequestState.FINISHED for r in reqs)
