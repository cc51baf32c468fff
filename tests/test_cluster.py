"""Cluster-orchestrator tests: allocator invariants (unit + property),
pool lease churn, trace round-trip, policy no-op guard / callable schedules,
and an end-to-end contention run where preemption must not perturb a
trainer's convergence curve."""
import numpy as np
import pytest

from repro.cluster import (ClusterOrchestrator, ClusterTrace, DevicePool,
                           FairShareAllocator, JobDemand, JobSpec, ServeJob,
                           TraceEvent, UsageLedger, arrive, burst,
                           cocoa_train_job, depart)
from repro.core import ElasticScalingPolicy, ScaleEvent
from repro.core.fairshare import (integerize_shares, jain_index, stride_pick,
                                  weighted_max_min)


# ---------------------------------------------------------------------------
# fair-share primitives + allocator
# ---------------------------------------------------------------------------


def _check_alloc_invariants(pool, demands, alloc):
    total_demand = sum(d.demand for d in demands)
    assert sum(alloc.values()) <= pool
    assert sum(alloc.values()) == min(pool, total_demand)  # work conserving
    for d in demands:
        assert 0 <= alloc[d.name] <= d.demand
    demanding = [d for d in demands if d.demand > 0]
    if len(demanding) <= pool:
        for d in demanding:  # no starvation under positive weights
            assert alloc[d.name] >= 1, f"{d.name} starved: {alloc}"


def test_weighted_max_min_proportional_and_capped():
    # uncapped: proportional to weight
    assert weighted_max_min(6, [10, 10], [2, 1]) == [4.0, 2.0]
    # demand caps bind, surplus flows to the unsatisfied principal
    assert weighted_max_min(8, [8, 8, 4], [1, 1, 4]) == [2.0, 2.0, 4.0]
    # work conserving under excess capacity
    assert weighted_max_min(100, [3, 5], [1, 1]) == [3.0, 5.0]
    with pytest.raises(ValueError):
        weighted_max_min(4, [1, 1], [1, 0])


def test_integerize_preserves_total_and_caps():
    out = integerize_shares([2.5, 2.5, 3.0], [8, 8, 3], 8)
    assert sum(out) == 8 and out[2] == 3


def test_jain_index_bounds():
    assert jain_index([1, 1, 1]) == pytest.approx(1.0)
    assert jain_index([1, 0, 0]) == pytest.approx(1 / 3)
    assert jain_index([]) == 1.0


def test_stride_pick_is_weighted():
    served = {}
    picks = []
    for _ in range(8):
        t = stride_pick(served, {"a": 3.0, "b": 1.0}, ["a", "b"])
        served[t] = served.get(t, 0.0) + 1.0
        picks.append(t)
    assert picks.count("a") == 6 and picks.count("b") == 2


def test_allocator_contention_shares():
    al = FairShareAllocator(priority_boost=2.0)
    demands = [JobDemand("a", 8, 1, 0), JobDemand("b", 8, 1, 0),
               JobDemand("s", 4, 2, 1)]
    alloc = al.allocate(8, demands)
    assert alloc == {"a": 2, "b": 2, "s": 4}  # priority preempts, capped
    _check_alloc_invariants(8, demands, alloc)


def test_allocator_no_starvation_with_tiny_weight():
    al = FairShareAllocator()
    demands = [JobDemand("big", 8, 1000.0, 2), JobDemand("tiny", 8, 0.001, 0)]
    alloc = al.allocate(4, demands)
    _check_alloc_invariants(4, demands, alloc)
    assert alloc["tiny"] >= 1


def test_allocator_zero_demand_and_empty():
    al = FairShareAllocator()
    assert al.allocate(8, []) == {}
    alloc = al.allocate(8, [JobDemand("idle", 0), JobDemand("busy", 3)])
    assert alloc == {"idle": 0, "busy": 3}
    with pytest.raises(ValueError):
        al.allocate(8, [JobDemand("bad", 2, weight=0.0)])


def test_allocator_property_invariants_seeded():
    """Pure-numpy fuzz of the allocator invariants (hypothesis-free tier)."""
    rng = np.random.default_rng(0)
    al = FairShareAllocator()
    for _ in range(200):
        pool = int(rng.integers(0, 17))
        njobs = int(rng.integers(1, 7))
        demands = [JobDemand(f"j{i}", int(rng.integers(0, 13)),
                             float(rng.uniform(0.05, 8.0)),
                             int(rng.integers(0, 3)))
                   for i in range(njobs)]
        _check_alloc_invariants(pool, demands, al.allocate(pool, demands))


# hypothesis variant (gated per-test so the rest of this module still runs
# when hypothesis is not installed)
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(
        pool=st.integers(0, 24),
        demands=st.lists(
            st.tuples(st.integers(0, 16),
                      st.floats(0.01, 10.0, allow_nan=False),
                      st.integers(0, 3)),
            min_size=1, max_size=8),
    )
    def test_allocator_property_invariants(pool, demands):
        al = FairShareAllocator()
        jds = [JobDemand(f"j{i}", d, w, p)
               for i, (d, w, p) in enumerate(demands)]
        _check_alloc_invariants(pool, jds, al.allocate(pool, jds))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_allocator_property_invariants():
        pass


# ---------------------------------------------------------------------------
# device pool
# ---------------------------------------------------------------------------


def test_pool_minimal_churn_reassign():
    pool = DevicePool(6, pst=[1.0, 1.0, 1.0, 1.0, 1.5, 1.5])
    first = pool.reassign({"a": 4, "b": 2})
    assert sorted(first["a"] + first["b"]) == list(range(6))
    held_a = set(first["a"])
    # shrink a by one: it keeps 3 of its own nodes, surrendering a slowest
    second = pool.reassign({"a": 3, "b": 3})
    assert set(second["a"]) < held_a
    surrendered = held_a - set(second["a"])
    assert all(pool.pst[n] == max(pool.psts_of(list(held_a)))
               for n in surrendered)
    # job departure frees its lease
    pool.release_all("b")
    assert pool.n_leased() == len(second["a"])


def test_pool_rejects_overcommit():
    pool = DevicePool(4)
    with pytest.raises(ValueError):
        pool.reassign({"a": 3, "b": 2})


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------


def test_trace_json_roundtrip_and_order():
    tr = ClusterTrace([depart(9.0, "t"), arrive(0.0, "t"),
                       burst(4.0, "s", 8, rate=2.0, tenant="gold")])
    assert [e.kind for e in tr.events] == ["arrive", "burst", "depart"]
    tr2 = ClusterTrace.from_json(tr.to_json())
    assert [e.to_dict() for e in tr2.events] == [e.to_dict()
                                                for e in tr.events]
    assert tr2.events[1].payload["tenant"] == "gold"
    assert tr2.pop_due(4.0) == tr2.events[:2]
    assert not tr2.exhausted and tr2.last_event_time("t") == 9.0
    with pytest.raises(ValueError):
        TraceEvent(0.0, "resize", "t")  # decisions are not trace events


def test_trace_add_after_consumption_never_replays():
    """add() mid-run must not rewind the cursor over delivered events, and
    a late-added past-stamped event still fires on the next pop_due."""
    tr = ClusterTrace([arrive(0.0, "a"), depart(10.0, "a")])
    assert [e.kind for e in tr.pop_due(1.0)] == ["arrive"]
    tr.add(burst(5.0, "s", 2))          # future event, normal insertion
    tr.add(TraceEvent(0.5, "burst", "s", {"n": 1}))  # stamped in the past
    due = tr.pop_due(6.0)
    assert [e.at for e in due] == [0.5, 5.0]  # fired once, arrive not replayed
    assert [e.kind for e in tr.pop_due(11.0)] == ["depart"]
    assert tr.exhausted


# ---------------------------------------------------------------------------
# ElasticScalingPolicy: no-op guard, callable schedule, decision logging
# ---------------------------------------------------------------------------


def test_scaling_policy_rejects_noop_construction():
    with pytest.raises(ValueError, match="never fires"):
        ElasticScalingPolicy([])
    with pytest.raises(ValueError, match="never fires"):
        ElasticScalingPolicy(None)


def test_scaling_policy_callable_schedule_and_event_log():
    from repro.core import Assignment, ChunkStore, UniTaskEngine
    store = ChunkStore({"x": np.zeros((40, 2), np.float32)}, chunk_size=5)
    a = Assignment(store.n_chunks, 2, np.random.default_rng(0))
    targets = iter([None, 4, 4, 1])
    pol = ElasticScalingPolicy(lambda t: next(targets))
    eng = UniTaskEngine(store, a, [pol], seed=0)

    def solver(s, asg, sh):
        k = asg.n_workers
        return {"samples_processed": 40, "per_worker_samples": [40 / k] * k}

    hist = eng.run(4, solver, lambda: 0.0)
    assert [r.n_workers for r in hist] == [2, 4, 4, 1]
    # applied decisions land in the iteration records (plot markers)
    assert hist[0].events == []
    assert hist[1].events == [(hist[0].sim_time, 2, 4)]
    assert hist[2].events == []
    assert hist[3].events[0][1:] == (4, 1)


# ---------------------------------------------------------------------------
# jobs + orchestrator end-to-end
# ---------------------------------------------------------------------------


def _tiny_trainer(name, seed=0, iterations=6, mode="microtask"):
    return cocoa_train_job(name, iterations=iterations, k_tasks=4,
                           n=400, f=8, chunk=20, seed=seed, mode=mode)


def _serve_cfg():
    from repro.configs import get_config, smoke_variant
    return smoke_variant(get_config("smollm-360m"))


def test_orchestrator_contention_preempts_without_perturbing_loss():
    t1 = _tiny_trainer("t1", seed=0)
    srv = ServeJob(JobSpec("svc", "serve", weight=1.0, priority=1,
                           max_nodes=2),
                   _serve_cfg(), capacity=4, cache_len=32, prefill_bucket=8,
                   seed=0)
    trace = ClusterTrace([
        arrive(0.0, "t1"), arrive(2.0, "svc"),
        burst(2.0, "svc", 4, prompt_len=[6, 10], max_new_tokens=[3, 5],
              seed=1),
    ])
    orch = ClusterOrchestrator(DevicePool(4), [t1, srv], trace,
                               dt=1.0, max_ticks=300)
    rep = orch.run()
    assert rep.jobs["t1"]["state"] == "finished"
    assert rep.jobs["svc"]["state"] == "finished"
    assert rep.preemptions >= 1  # the burst squeezed the trainer
    assert rep.jobs["svc"]["serve"]["requests_finished"] == 4
    assert 0.0 < rep.utilization <= 1.0
    assert 0.0 < rep.fairness_jain <= 1.0

    # Chicle headline: contention changed WHEN iterations ran, not WHAT
    # they computed — solo curve and dual state are bit-identical
    solo = _tiny_trainer("solo", seed=0)
    ClusterOrchestrator(DevicePool(4), [solo],
                        ClusterTrace([arrive(0.0, "solo")]),
                        dt=1.0, max_ticks=300).run()
    assert solo.loss_curve() == t1.loss_curve()
    assert np.array_equal(solo.solver.store.state["alpha"],
                          t1.solver.store.state["alpha"])
    assert np.array_equal(np.asarray(solo.solver.w),
                          np.asarray(t1.solver.w))


def test_orchestrator_departure_returns_nodes():
    t1 = _tiny_trainer("t1", seed=0, iterations=40)
    t2 = _tiny_trainer("t2", seed=1, iterations=40, mode="unitask")
    trace = ClusterTrace([arrive(0.0, "t1"), arrive(0.0, "t2"),
                          depart(4.0, "t2")])
    orch = ClusterOrchestrator(DevicePool(4), [t1, t2], trace,
                               dt=1.0, max_ticks=200)
    rep = orch.run()
    assert rep.jobs["t2"]["state"] == "departed"
    assert rep.jobs["t1"]["state"] == "finished"
    # after the departure t1 owns the whole pool again
    post = [t for t in rep.timeline if t.t >= 4.0 and t.alloc.get("t1")]
    assert post and all(t.alloc["t1"] == 4 for t in post)


def test_lm_train_job_runs_real_steps_under_orchestration():
    """Real-compute LM job: the orchestrator drives actual jitted train
    steps, scale-to-zero parks state on host, and the preempted job's loss
    curve is exactly the curve of the same job run without preemption."""
    import jax.numpy as jnp
    from repro.cluster import JobSpec, LMTrainJob
    from repro.configs import TrainConfig
    from repro.data import make_lm_tokens

    cfg = _serve_cfg()
    data = make_lm_tokens(32, 32, cfg.vocab_size, seed=0)

    def batch(i):
        sl = slice(4 * (i % 8), 4 * (i % 8 + 1))
        return {"tokens": jnp.asarray(data["tokens"][sl]),
                "labels": jnp.asarray(data["labels"][sl]),
                "weights": jnp.ones((4,), jnp.float32)}

    def lm_job():
        return LMTrainJob(JobSpec("lm", "train", max_nodes=2), cfg,
                          TrainConfig(learning_rate=5e-3, remat=False),
                          batch_fn=batch, steps=6, step_time=1.0, seed=0)

    # squeeze it to zero mid-run with a short-lived high-priority hog
    job = lm_job()
    hog = _tiny_trainer("hog", seed=0, iterations=3)
    hog.spec.priority = 2
    trace = ClusterTrace([arrive(0.0, "lm"), arrive(2.0, "hog")])
    orch = ClusterOrchestrator(DevicePool(1), [job, hog], trace,
                               dt=1.0, max_ticks=100)
    rep = orch.run()
    assert rep.jobs["lm"]["state"] == "finished"
    assert job.steps_done == 6
    assert rep.jobs["lm"]["steps_done"] == 6
    assert job.preemptions >= 1  # the hog displaced it entirely

    alone = lm_job()
    ClusterOrchestrator(DevicePool(1), [alone], ClusterTrace(
        [arrive(0.0, "lm")]), dt=1.0, max_ticks=100).run()
    assert alone.preemptions == 0
    assert job.loss_curve() == alone.loss_curve()


def test_serve_job_scale_to_zero_and_resume():
    srv = ServeJob(JobSpec("svc", "serve", weight=1.0, max_nodes=2),
                   _serve_cfg(), capacity=4, cache_len=32, prefill_bucket=8,
                   seed=0)
    # a higher-priority trainer that hogs the whole pool until it finishes;
    # with one node and two demanding jobs the no-starvation floor (which
    # needs pool >= #demanding jobs) cannot protect the server, so the
    # allocator squeezes it to zero until the hog completes
    hog = _tiny_trainer("hog", seed=0, iterations=6)
    hog.spec.priority = 2
    hog.spec.weight = 50.0
    trace = ClusterTrace([
        arrive(0.0, "hog"), arrive(0.0, "svc"),
        burst(0.0, "svc", 3, prompt_len=[6, 8], max_new_tokens=[3, 4],
              seed=1),
    ])
    pool = DevicePool(1)
    orch = ClusterOrchestrator(pool, [hog, srv], trace, dt=1.0,
                               max_ticks=300)
    rep = orch.run()
    # the server was suspended at least once (scale-to-zero) yet finished
    events = [e[1] for e in srv.engine.metrics.suspend_events]
    assert "suspend" in events and "resume" in events
    assert rep.jobs["svc"]["serve"]["requests_finished"] == 3
    assert rep.jobs["svc"]["state"] == "finished"


def test_serve_job_without_bursts_retires_instead_of_spinning():
    """A server whose trace never delivers requests must finish once its
    event horizon passes — not pin the orchestrator until max_ticks."""
    srv = ServeJob(JobSpec("svc", "serve", max_nodes=2), _serve_cfg(),
                   capacity=2, cache_len=32, seed=0)
    orch = ClusterOrchestrator(DevicePool(2), [srv],
                               ClusterTrace([arrive(0.0, "svc")]),
                               dt=1.0, max_ticks=50)
    rep = orch.run()
    assert rep.jobs["svc"]["state"] == "finished"
    assert rep.ticks < 5


def test_suspended_engine_refuses_to_tick():
    srv = ServeJob(JobSpec("svc", "serve"), _serve_cfg(), capacity=2,
                   cache_len=32, seed=0)
    srv.engine.suspend()
    with pytest.raises(RuntimeError, match="suspended"):
        srv.engine.tick()
    srv.engine.resume()
    srv.engine.tick()  # legal again


def test_engine_with_clock_rejects_wall_clock_run():
    srv = ServeJob(JobSpec("svc", "serve"), _serve_cfg(), capacity=2,
                   cache_len=32, seed=0)
    with pytest.raises(ValueError, match="tick"):
        srv.engine.run([])


# ---------------------------------------------------------------------------
# allocator lookahead: time-decayed usage credit
# ---------------------------------------------------------------------------


def test_usage_ledger_credit_bounds_and_forget():
    led = UsageLedger(half_life=4.0, credit_cap=4.0)
    assert led.credit("unknown") == 1.0
    demands = [JobDemand("hog", 4), JobDemand("meek", 4)]
    for _ in range(20):  # hog takes everything while meek gets nothing
        led.update({"hog": 4, "meek": 0}, demands, 1.0)
    assert led.credit("hog") < 1.0
    assert led.credit("meek") == 4.0  # boosted, clamped at the cap
    assert 1.0 / 4.0 <= led.credit("hog")
    led.forget("hog")
    assert led.credit("hog") == 1.0
    with pytest.raises(ValueError):
        UsageLedger(half_life=0.0)
    with pytest.raises(ValueError):
        UsageLedger(credit_cap=1.0)


def test_usage_ledger_burst_repayment():
    """A priority burst that squeezed an equal-weight peer is repaid: once
    the burst ends, the squeezed job is boosted ABOVE its memoryless half
    until the decayed histories even out.  (Consuming an otherwise-idle
    pool is NOT debt — fair share is measured against what the demanding
    set actually consumed, so scavenging free nodes stays free.)"""
    al = FairShareAllocator()
    led = UsageLedger(half_life=6.0)
    alloc_b = []
    for t in range(60):
        # ticks 0-14: a bursts at priority 1 and squeezes b to the floor
        pa = 1 if t < 15 else 0
        demands = [JobDemand("a", 8, 1.0, pa), JobDemand("b", 8, 1.0, 0)]
        alloc = al.allocate(8, demands, credit=led.snapshot())
        led.update(alloc, demands, 1.0)
        if t >= 15:
            alloc_b.append(alloc["b"])
    assert alloc_b[0] > 4  # b is owed credit: above the memoryless half
    assert alloc_b[-1] == 4  # decay forgets the burst: back to equal split
    # a keeps at least the no-starvation floor while repaying
    assert min(8 - b for b in alloc_b) >= 1
    # idle-pool scavenging leaves no debt: a lone demander stays at credit 1
    led2 = UsageLedger(half_life=6.0)
    solo = [JobDemand("solo", 8, 1.0)]
    for _ in range(10):
        led2.update(al.allocate(8, solo, credit=led2.snapshot()), solo, 1.0)
    assert led2.credit("solo") == pytest.approx(1.0)
    # ...including capacity a SATISFIED low-demand peer cannot use: the
    # fair entitlement is demand-capped, so taking the peer's leftover
    # nodes is scavenging, not over-consumption
    led3 = UsageLedger(half_life=6.0)
    pair = [JobDemand("small", 1, 1.0), JobDemand("big", 8, 1.0)]
    for _ in range(20):
        led3.update(al.allocate(8, pair, credit=led3.snapshot()), pair, 1.0)
    assert led3.credit("big") == pytest.approx(1.0)
    assert led3.credit("small") == pytest.approx(1.0)


def test_usage_ledger_long_run_shares_respect_weights():
    """Property (seeded): under randomly bursty third-party demand, two
    always-demanding jobs with weights 1:3 accumulate node-time in that
    ratio once credit is active, and every allocator invariant holds with
    the credit multipliers applied."""
    rng = np.random.default_rng(5)
    al = FairShareAllocator()
    led = UsageLedger(half_life=8.0)
    total = {"a": 0.0, "b": 0.0}
    for t in range(400):
        demands = [JobDemand("a", 8, 1.0), JobDemand("b", 8, 3.0)]
        if rng.random() < 0.4:  # bursty interloper comes and goes
            demands.append(JobDemand("c", int(rng.integers(1, 9)), 1.0))
        alloc = al.allocate(8, demands, credit=led.snapshot())
        _check_alloc_invariants(8, demands, alloc)
        led.update(alloc, demands, 1.0)
        total["a"] += alloc["a"]
        total["b"] += alloc["b"]
    ratio = total["b"] / total["a"]
    assert 2.5 <= ratio <= 3.5, f"long-run share ratio drifted: {ratio:.2f}"


def test_orchestrator_with_ledger_matches_invariants():
    """The orchestrator wiring: usage_half_life turns the ledger on without
    breaking completion or the report schema."""
    t1 = _tiny_trainer("t1", seed=0)
    t2 = _tiny_trainer("t2", seed=1)
    trace = ClusterTrace([arrive(0.0, "t1"), arrive(3.0, "t2")])
    orch = ClusterOrchestrator(DevicePool(4), [t1, t2], trace,
                               usage_half_life=6.0, dt=1.0, max_ticks=200)
    rep = orch.run()
    assert rep.jobs["t1"]["state"] == "finished"
    assert rep.jobs["t2"]["state"] == "finished"
    assert orch.ledger is not None


# ---------------------------------------------------------------------------
# lease shrink parks serve slots (page-granular preemption, bytes charged)
# ---------------------------------------------------------------------------


def test_serve_lease_shrink_parks_slots_and_charges_bytes():
    srv = ServeJob(JobSpec("svc", "serve", weight=1.0, max_nodes=3),
                   _serve_cfg(), capacity=6, cache_len=40, prefill_bucket=8,
                   slots_per_node=2, ticks_per_dt=1.0, kv_layout="paged",
                   seed=0)
    # high-priority trainer arrives mid-serve and squeezes the lease
    hog = _tiny_trainer("hog", seed=0, iterations=8)
    hog.spec.priority = 2
    hog.spec.weight = 20.0
    hog.spec.max_nodes = 2
    trace = ClusterTrace([
        arrive(0.0, "svc"),
        burst(0.0, "svc", 6, prompt_len=[6, 8], max_new_tokens=[20, 24],
              seed=1),
        arrive(1.0, "hog"),
    ])
    orch = ClusterOrchestrator(DevicePool(3), [srv, hog], trace, dt=1.0,
                               max_ticks=400)
    rep = orch.run()
    assert rep.jobs["svc"]["state"] == "finished"
    # the shrink parked in-flight slots and charged the moved KV bytes
    assert srv.kv_moved_bytes > 0
    assert rep.kv_moved_bytes == srv.kv_moved_bytes
    assert rep.jobs["svc"]["kv_moved_bytes"] == srv.kv_moved_bytes
    s = rep.jobs["svc"]["serve"]
    assert s["parked_total"] >= 1 and s["restored_total"] >= 1
    # every request still completed with its full token budget
    assert s["requests_finished"] == 6
    assert srv.engine.pages.n_used == 0 and srv.engine.mem.n_parked == 0
