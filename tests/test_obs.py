"""Observability tests: span mechanics, the profiler bridge, Chrome export
validity, the disabled-tracer zero-cost/bit-identical contract, percentile
math vs numpy, attribution, registry re-backing, and the cluster trace
stream."""
import glob
import json

import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.obs import (NOOP_SPAN, NULL_TRACER, MetricsRegistry,
                       ScopedTracer, Tracer, dominant_host_phase, percentile,
                       phase_attribution, validate_chrome_trace)
from repro.serve import ServeEngine, poisson_arrivals, synthetic_requests


@pytest.fixture(scope="module")
def cfg():
    return smoke_variant(get_config("smollm-360m"))


def _requests(cfg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    arr = poisson_arrivals(n, 20.0, rng=rng)
    return synthetic_requests(n, vocab_size=cfg.vocab_size, arrivals=arr,
                              prompt_len=(6, 20), max_new_tokens=(4, 8),
                              rng=rng)


# ---------------------------------------------------------------------------
# Span mechanics
# ---------------------------------------------------------------------------


def test_span_nesting_and_exception_safety():
    trc = Tracer()
    with trc.span("tick"):
        with trc.span("decode.dispatch", slots=3):
            pass
        with pytest.raises(ValueError):
            with trc.span("admit"):
                raise ValueError("boom")
    inner = trc.spans("decode.dispatch")[0]
    admit = trc.spans("admit")[0]
    outer = trc.spans("tick")[0]
    # depth recorded at entry; the failed span is kept, flagged, re-raised
    assert outer.depth == 0 and inner.depth == 1 and admit.depth == 1
    assert admit.error and not inner.error and not outer.error
    # spans close inner-first, and a child lies within its parent
    assert outer.ts <= inner.ts
    assert inner.ts + inner.dur <= outer.ts + outer.dur + 1e-9
    # default track is the first dot-segment; args round-trip
    assert inner.track == "decode" and inner.args == {"slots": 3}
    assert trc._depth == 0  # balanced after the exception


def test_chrome_export_is_valid():
    trc = Tracer(name="unit")
    with trc.span("decode.dispatch"):
        with trc.span("device_wait", cat="device", track="decode"):
            pass
    trc.instant("jit.miss", track="jit", key="(1, 2)")
    obj = json.loads(json.dumps(trc.to_chrome()))  # must be JSON-able
    counts = validate_chrome_trace(
        obj, require_names=["decode.dispatch", "device_wait", "jit.miss"])
    assert counts["device_wait"] == 1
    evs = obj["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"process_name", "thread_name"} <= names  # track metadata
    span = next(e for e in evs if e["name"] == "decode.dispatch")
    inst = next(e for e in evs if e["name"] == "jit.miss")
    assert span["ph"] == "X" and span["dur"] >= 0 and "ts" in span
    assert inst["ph"] == "i" and inst["s"] == "t"
    # both decode-track events share a tid (one row in the viewer)
    wait = next(e for e in evs if e["name"] == "device_wait")
    assert wait["tid"] == span["tid"]
    with pytest.raises(ValueError):
        validate_chrome_trace(obj, require_names=["no.such.event"])
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})


def test_disabled_tracer_is_noop_singleton():
    trc = Tracer(enabled=False)
    assert trc.span("anything", big="args") is NOOP_SPAN
    assert trc.span("other") is trc.span("third")  # shared, no allocation
    with trc.span("x"):
        pass
    trc.instant("i")
    trc.count("c")
    trc.gauge("g", 1)
    trc.observe("h", 1.0)
    assert trc.events == [] and len(trc.registry) == 0
    assert NULL_TRACER.enabled is False


def test_disabled_overhead_guard():
    """The disabled fast path must stay ~free: 200k span entries in well
    under 2s (a generous absolute bound — the real check is that nothing
    allocates or reads the clock on this path)."""
    import time

    trc = Tracer(enabled=False)
    t0 = time.perf_counter()
    for _ in range(200_000):
        with trc.span("decode.dispatch", n=8):
            pass
    assert time.perf_counter() - t0 < 2.0
    assert trc.events == []


# ---------------------------------------------------------------------------
# Profiler bridge: spans on the jax profiler's host plane
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def host_events(tmp_path_factory):
    """One CPU profiler trace around every case the bridge tests read:
    {name: [(start_ns, end_ns)]} of the `/host:CPU` plane's events, and the
    in-memory events of the bridged tracer."""
    import jax
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("prof")
    on = Tracer(profiler=True)
    pool = ScopedTracer(on, "prefill")
    jax.profiler.start_trace(str(d))
    try:
        with on.span("bridge.tick"):
            with on.span("bridge.dispatch", n=3):
                pass
            with pool.span("bridge.pooled"):
                pass
        with pytest.raises(ValueError):
            with on.span("bridge.raises"):
                raise ValueError("boom")
        with on.span("bridge.after"):
            pass
        with Tracer(enabled=False, profiler=True).span("bridge.disabled"):
            pass
        with Tracer(profiler=False).span("bridge.unbridged"):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out, on


def test_profiler_bridge_spans_land_nested_on_host_plane(host_events):
    ev, on = host_events
    (t0, t1), = ev["bridge.tick"]
    (d0, d1), = ev["bridge.dispatch"]
    (p0, p1), = ev["prefill.bridge.pooled"]  # a scope keeps its pool apart
    assert "bridge.pooled" not in ev
    assert t0 <= d0 <= d1 <= p0 <= p1 <= t1
    # in-memory events and their args are unchanged by the bridge
    assert [e.name for e in on.spans()][:3] == [
        "bridge.dispatch", "bridge.pooled", "bridge.tick"]
    assert on.spans("bridge.dispatch")[0].args == {"n": 3}
    assert on.spans("bridge.pooled")[0].track == "prefill.bridge"
    assert not any(n.startswith("bridge.dispatch") and n != "bridge.dispatch"
                   for n in ev)  # the annotation carries the name only


def test_profiler_annotation_closes_when_body_raises(host_events):
    ev, on = host_events
    (r0, r1), = ev["bridge.raises"]  # recorded: its annotation was exited
    (a0, a1), = ev["bridge.after"]
    assert r1 <= a0  # the next span is not nested in the failed one
    assert on.spans("bridge.raises")[0].error and on._depth == 0


def test_disabled_or_unbridged_tracer_adds_no_host_event(host_events):
    ev, _ = host_events
    assert "bridge.disabled" not in ev and "bridge.unbridged" not in ev
    assert Tracer(enabled=False, profiler=True).span("x") is NOOP_SPAN


# ---------------------------------------------------------------------------
# Percentile / registry math
# ---------------------------------------------------------------------------


def test_percentile_matches_numpy():
    rng = np.random.default_rng(7)
    for n in (1, 2, 3, 10, 101):
        xs = rng.normal(size=n)
        for q in (0, 7.5, 25, 50, 90, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), abs=1e-12)
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_registry_kinds_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    reg.gauge("b").set(2.5)
    reg.histogram("c").observe(1.0)
    reg.histogram("c").observe(3.0)
    with pytest.raises(TypeError):
        reg.gauge("a")  # kind conflict
    snap = reg.snapshot()
    assert snap["a"] == 3 and snap["b"] == 2.5
    assert snap["c"]["count"] == 2 and snap["c"]["p50"] == 2.0
    assert "a" in reg and len(reg) == 3


# ---------------------------------------------------------------------------
# Attribution report
# ---------------------------------------------------------------------------


def test_phase_attribution_splits_host_device():
    t = [0.0]
    trc = Tracer(clock=lambda: t[0])

    def span(name, dur, **kw):
        cm = trc.span(name, **kw)
        cm.__enter__()
        t[0] += dur
        cm.__exit__(None, None, None)

    for _ in range(4):
        span("schedule", 0.001)
        span("decode.dispatch", 0.002)
        span("device_wait", 0.010, cat="device", track="decode")
    attr = phase_attribution(trc)
    assert attr["decode"]["host_ms_total"] == pytest.approx(8.0)
    assert attr["decode"]["device_ms_total"] == pytest.approx(40.0)
    assert attr["schedule"]["host_ms_p50"] == pytest.approx(1.0)
    # device time must not crown the dominant HOST phase
    assert dominant_host_phase(attr) == "decode"


def test_phase_attribution_outermost_only():
    """A detail span nested in its phase envelope (same track) must not
    double-count, and the excluded root track stays out entirely."""
    t = [0.0]
    trc = Tracer(clock=lambda: t[0])
    root = trc.span("tick")
    root.__enter__()
    outer = trc.span("schedule")
    outer.__enter__()
    inner = trc.span("schedule.policy", track="schedule")
    inner.__enter__()
    t[0] += 0.004
    inner.__exit__(None, None, None)
    t[0] += 0.001
    outer.__exit__(None, None, None)
    root.__exit__(None, None, None)
    attr = phase_attribution(trc)
    assert "tick" not in attr
    assert attr["schedule"]["host_ms_total"] == pytest.approx(5.0)
    assert attr["schedule"]["count"] == 1


# ---------------------------------------------------------------------------
# Engine integration: bit-identical streams, phases present, registry
# ---------------------------------------------------------------------------


def _streams(cfg, *, tracer=None, **kw):
    eng = ServeEngine(cfg, capacity=4, cache_len=64, prefill_bucket=8,
                      seed=0, tracer=tracer, **kw)
    eng.run(_requests(cfg))
    return {r.rid: tuple(r.generated) for r in eng.metrics.requests}, eng


@pytest.mark.parametrize("kw", [
    dict(kv_layout="flat"),
    dict(kv_layout="paged", page_size=8, chunked_prefill=True,
         prefill_chunk=16),
    dict(kv_layout="paged", page_size=8, spec="ngram", spec_k=3),
], ids=["flat", "paged", "paged-spec"])
def test_tracing_does_not_change_streams(cfg, kw):
    base, _ = _streams(cfg, tracer=None, **kw)
    traced, _ = _streams(cfg, tracer=Tracer(), **kw)
    assert base == traced


def test_traced_engine_covers_phases(cfg):
    trc = Tracer()
    _, eng = _streams(cfg, tracer=trc, kv_layout="paged", page_size=8,
                      chunked_prefill=True, prefill_chunk=16)
    tracks = set(trc.tracks())
    assert {"schedule", "admit", "prefill", "decode",
            "cow_plan", "prefix_index"} <= tracks
    assert trc.spans("device_wait")  # explicit sync boundaries exist
    attr = phase_attribution(trc)
    assert isinstance(dominant_host_phase(attr), str)
    reg = trc.registry
    assert reg.counter("serve.ticks").value == len(eng.metrics.ticks)
    assert reg.histogram("serve.tick_s").count == len(eng.metrics.ticks)
    # chunked admissions + per-k jit caches showed up; a miss is an
    # instant, not a span (the compile runs inside the dispatch span)
    assert reg.counter("serve.jit_misses").value > 0
    assert any(e.name == "jit.miss" for e in trc.events)
    assert not trc.spans("jit.build")
    assert trc.spans("prefill.chunk")


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_tick_records_time_blocked_on_prefills(cfg, traced):
    """`TickRecord.prefill_wait_s` is the tick's time in
    `prefill.device_wait`, kept whether or not a tracer is attached: the
    overlapped engine with whole-prompt prefill blocks there on exactly the
    ticks that admitted."""
    trc = Tracer() if traced else None
    _, eng = _streams(cfg, tracer=trc, kv_layout="paged", page_size=8,
                      chunked_prefill=False, overlap=True)
    ticks = eng.metrics.ticks
    assert [t.prefill_wait_s > 0 for t in ticks] == [t.admitted > 0
                                                     for t in ticks]
    if traced:
        waits = trc.spans("prefill.device_wait")
        assert len(waits) == sum(1 for t in ticks if t.admitted)
        assert sum(t.prefill_wait_s for t in ticks) >= sum(
            w.dur for w in waits)


def test_serve_metrics_registry_backing(cfg):
    _, eng = _streams(cfg, kv_layout="paged", page_size=8)
    s = eng.metrics.summarize()
    reg = eng.metrics.to_registry()
    assert reg.counter("serve.tokens_generated").value \
        == s["tokens_generated"]
    assert reg.gauge("serve.requests_finished").value \
        == s["requests_finished"]
    h = reg.histogram("serve.ttft_s")
    assert h.count == s["requests_finished"]
    assert h.percentile(50) == pytest.approx(s["ttft_p50_s"])


# ---------------------------------------------------------------------------
# Cluster: per-tick stream + job tracks
# ---------------------------------------------------------------------------


def test_cluster_trace_out_and_tracks(cfg, tmp_path):
    from repro.cluster import (ClusterOrchestrator, ClusterTrace, DevicePool,
                               JobSpec, ServeJob, arrive, burst,
                               cocoa_train_job)

    train = cocoa_train_job("train", iterations=4, k_tasks=4, n=400, f=16,
                            chunk=50, seed=0)
    srv = ServeJob(JobSpec("svc", "serve", priority=1, max_nodes=2), cfg,
                   capacity=4, cache_len=32, prefill_bucket=8, seed=0)
    trace = ClusterTrace([
        arrive(0.0, "train"), arrive(1.0, "svc"),
        burst(1.0, "svc", 3, prompt_len=[6, 10], max_new_tokens=[3, 5],
              seed=1),
    ])
    out = tmp_path / "cluster.jsonl"
    trc = Tracer(name="cluster")
    orch = ClusterOrchestrator(DevicePool(4), [train, srv], trace,
                               max_ticks=60, tracer=trc,
                               trace_out=str(out))
    report = orch.run()
    # JSONL stream: one parseable line per tick, fields = TickStats
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert len(lines) == report.ticks
    assert all({"t", "demand", "alloc", "nodes_used"} <= set(l) for l in lines)
    assert lines[-1]["nodes_used"] >= 0
    # tracer: allocator track + one track per job, lease changes marked
    tracks = set(trc.tracks())
    assert {"allocator", "train", "svc"} <= tracks
    assert any(e.name == "lease_change" for e in trc.events)
    assert trc.registry.counter("cluster.ticks").value == report.ticks
    # report headline quantities re-backed onto the registry
    assert trc.registry.gauge("cluster.utilization").value \
        == pytest.approx(report.utilization)
