"""KV memory manager tests: refcounted sharing + copy-on-write + host-parked
eviction.  The flat engine stays the bit-equality oracle — sharing and
eviction may only change bytes moved and pages held, never a single token,
including across elastic resizes and preempt/park/restore cycles."""
import numpy as np
import pytest
from jax import set_mesh

from repro.configs import get_config, smoke_variant
from repro.core import ElasticScalingPolicy, ScaleEvent
from repro.serve import (KVMemoryManager, PageAllocator, PageError, Request,
                         RequestState, ServeEngine, synthetic_requests)
from repro.serve.memory import _selftest


@pytest.fixture(scope="module")
def cfg():
    return smoke_variant(get_config("smollm-360m"))


def _streams(metrics):
    return {r.rid: list(r.generated) for r in metrics.requests}


def _shared_burst(cfg, n=6, header=24, seed=1, suffix=(4, 10),
                  max_new=(4, 6), priority=0, tenant="default", rid_base=0,
                  arrivals=None):
    """n requests sharing an identical `header`-token prompt prefix."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, cfg.vocab_size, size=header)
    return synthetic_requests(
        n, vocab_size=cfg.vocab_size,
        arrivals=np.zeros(n) if arrivals is None else arrivals,
        prompt_len=suffix, max_new_tokens=max_new, shared_prefix=head,
        rng=np.random.default_rng(seed + 1), priority=priority,
        tenant=tenant, rid_base=rid_base)


# ---------------------------------------------------------------------------
# PageAllocator: refcounts, sharing, copy-on-write
# ---------------------------------------------------------------------------


def test_share_refcounts_and_free():
    pa = PageAllocator(n_pages=17, page_size=8)
    t0 = pa.alloc_slot(0, 24)  # 3 pages
    pa.alloc_slot(1, 0)
    pa.share(1, t0[:2])  # slot 1 maps slot 0's first two pages
    own = pa.ensure(1, 24)  # + 1 exclusive page
    assert pa.ref(t0[0]) == 2 and pa.ref(t0[1]) == 2 and pa.ref(t0[2]) == 1
    assert pa.n_logical == 6 and pa.n_used == 4 and pa.n_shared_extra == 2
    pa.check({0: 24, 1: 24})
    # donor finishes: shared pages survive for the sharer
    freed = pa.free_slot(0)
    assert freed == [t0[2]]  # only the exclusive page died
    assert pa.ref(t0[0]) == 1 and pa.ref(t0[1]) == 1
    pa.check({1: 24})
    freed = pa.free_slot(1)
    assert sorted(freed) == sorted(t0[:2] + own)
    assert pa.n_used == 0
    pa.check({})


def test_share_rejects_bad_pages():
    pa = PageAllocator(n_pages=9, page_size=4)
    t = pa.alloc_slot(0, 8)
    pa.alloc_slot(1, 4)
    with pytest.raises(PageError):
        pa.share(1, [7])  # unreferenced page
    with pytest.raises(PageError):
        pa.share(0, [t[0]])  # already in this slot's table
    with pytest.raises(PageError):
        pa.share(9, t)  # no table


def test_cow_break():
    pa = PageAllocator(n_pages=9, page_size=4)
    t = pa.alloc_slot(0, 7)  # 2 pages, second partial
    pa.alloc_slot(1, 0)
    pa.share(1, t)
    old, new = pa.cow(1, 1)
    assert old == t[1] and new not in t
    assert pa.ref(old) == 1 and pa.ref(new) == 1
    assert pa.table(1) == [t[0], new] and pa.table(0) == t
    pa.check({0: 7, 1: 7})
    with pytest.raises(PageError):
        pa.cow(1, 1)  # now exclusive: nothing to break
    with pytest.raises(PageError):
        pa.cow(1, 5)  # out of range


def test_refcount_drift_detected():
    pa = PageAllocator(n_pages=9, page_size=4)
    pa.alloc_slot(0, 8)
    pa._ref[pa.table(0)[0]] = 2  # corrupt: ref without a second reader
    with pytest.raises(PageError, match="refcount drift"):
        pa.check_invariants()


def test_defrag_dedupes_shared_pages():
    """A shared page must move exactly once; tables, refcounts, and the
    gather map must stay consistent (the invalidation the mid-prefill +
    sharing case revealed)."""
    pa = PageAllocator(n_pages=17, page_size=8)
    t0 = pa.alloc_slot(0, 24)
    pa.alloc_slot(1, 0)
    pa.share(1, t0[:2])
    pa.ensure(1, 24)
    pa.alloc_slot(2, 16)
    pa.free_slot(0)  # punch a hole: slot 1 still reads the shared pages
    src = pa.defrag()
    assert src is not None and len(src) == pa.n_pages
    assert len(set(src.tolist())) == pa.n_pages  # a page listed exactly once
    pa.check({1: 24, 2: 16})
    live = sorted({p for s in (1, 2) for p in pa.table(s)})
    assert live == list(range(1, pa.n_used + 1))  # compact
    assert pa.defrag() is None


# ---------------------------------------------------------------------------
# KVMemoryManager: prefix index, parking, fuzz
# ---------------------------------------------------------------------------


def test_prefix_match_full_and_partial():
    mem = KVMemoryManager(33, 4)
    prompt = np.arange(11)  # pages: [0..3], [4..7], partial [8..10]
    plan = mem.admit_slot(0, prompt)
    assert plan.shared_pages == 0 and plan.write_ids == plan.table
    # identical prompt: 2 full + whole-tail partial match
    plan2 = mem.admit_slot(1, prompt)
    assert plan2.shared_pages == 3 and plan2.shared_tokens == 11
    assert plan2.table == plan.table
    assert plan2.write_ids == [0, 0, 0]  # nothing to scatter
    # longer prompt diverging inside the partial page: full pages only
    plan3 = mem.admit_slot(2, np.concatenate([np.arange(9), [99, 98, 97]]))
    assert plan3.shared_pages == 2 and plan3.shared_tokens == 8
    assert plan3.table[:2] == plan.table[:2]
    assert plan3.write_ids[:2] == [0, 0] and plan3.write_ids[2] != 0
    # shorter prompt whose whole tail prefixes the resident partial page
    plan4 = mem.admit_slot(3, np.arange(10))
    assert plan4.shared_pages == 3 and plan4.shared_tokens == 10
    mem.check({0: 11, 1: 11, 2: 12, 3: 10})


def test_prefix_index_invalidated_on_free():
    mem = KVMemoryManager(17, 4)
    prompt = np.arange(8)
    mem.admit_slot(0, prompt)
    mem.release_slot(0)  # last reference: index entries must die with it
    mem.check({})
    plan = mem.admit_slot(1, prompt)
    assert plan.shared_pages == 0  # no stale hit on the freed pages
    mem.check({1: 8})


def test_chunked_admission_keeps_final_chunk():
    """A wholly-indexed prompt still leaves >= 1 token for the chunked path
    (the final chunk produces the last-token logits)."""
    mem = KVMemoryManager(33, 4)
    prompt = np.arange(8)  # exactly 2 full pages
    mem.admit_slot(0, prompt)
    off = mem.admit_chunked(1, prompt)
    assert off == 4  # one full page shared, one left to prefill
    assert mem.pages.n_pages_of(1) == 1


def test_stale_partial_claim_invalidated_on_overwrite():
    """After the last co-reader leaves, the surviving owner's decode writes
    into the once-shared partial page; the index claim for the overwritten
    tokens must die with that first write, or a later verbatim admission
    would map a page whose recorded tokens no longer exist."""
    mem = KVMemoryManager(33, 4)
    pA = np.arange(1, 12)  # 2 full pages + tail (9, 10, 11)
    mem.admit_slot(0, pA)
    plan_b = mem.admit_slot(1, pA[:9])  # tail (9,) prefixes A's claim
    assert plan_b.shared_pages == 3
    mem.release_slot(0)  # A finishes; B keeps the shared pages alive
    # B's first decode write: pos 9 = offset 1 of the now-exclusive partial
    # page — no COW fires, but the (9, 10, 11) claim extends past offset 1
    assert mem.cow_plan(1, 9) is None
    mem.pages.ensure(1, 10)
    mem.check({1: 10})
    # a verbatim re-admission of A's prompt maps the intact full pages ONLY
    plan_c = mem.admit_slot(2, pA)
    assert plan_c.shared_pages == 2
    assert plan_c.write_ids[2] != 0  # the tail page is re-prefilled
    mem.check({1: 10, 2: 11})


def test_stale_prefix_claim_engine_streams_match_oracle(cfg):
    """Engine-level twin of the stale-claim case: A registers a partial
    page, B shares it and overwrites it after A finishes, C re-admits A's
    exact prompt later — C must not read B's decode KV."""
    rng = np.random.default_rng(21)
    p = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    mk = lambda: [Request(rid=0, prompt=p.copy(), max_new_tokens=1),  # noqa: E731
                  Request(rid=1, prompt=p[:10].copy(), max_new_tokens=6),
                  Request(rid=2, prompt=p.copy(), max_new_tokens=4)]
    flat = ServeEngine(cfg, capacity=3, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(mk()))
    eng = ServeEngine(cfg, capacity=3, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    reqs = mk()
    eng.submit(reqs[:2])  # A (1 token, finishes at admission) + B
    eng._now()
    for _ in range(4):  # B decodes into the once-shared partial page
        with set_mesh(eng.mesh):
            eng.tick()
    assert reqs[0].state is RequestState.FINISHED
    assert reqs[1].n_generated >= 2
    eng.submit(reqs[2:])  # C: verbatim copy of A's prompt
    while eng._by_slot or eng.scheduler.has_pending:
        with set_mesh(eng.mesh):
            eng.tick()
    assert _streams(eng.metrics) == want
    assert eng.pages.n_used == 0


def test_same_tenant_priority_preemption_admits_the_head(cfg):
    """Preemption with victim and preemptor in the SAME tenant queue: the
    freed slot must go to the high-priority head, not back to the victim
    the park just re-queued (whose older arrival sorts ahead of the head)."""
    eng = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    low = synthetic_requests(2, vocab_size=cfg.vocab_size,
                             arrivals=np.zeros(2), prompt_len=(6, 8),
                             max_new_tokens=(12, 14),
                             rng=np.random.default_rng(2))
    hi = synthetic_requests(1, vocab_size=cfg.vocab_size,
                            arrivals=np.array([0.05]), prompt_len=(6, 8),
                            max_new_tokens=(4, 4), priority=2,
                            rng=np.random.default_rng(3), rid_base=100)
    eng.submit(low)
    eng._now()
    for _ in range(2):
        with set_mesh(eng.mesh):
            eng.tick()
    assert len(eng._by_slot) == 2
    eng.submit(hi)
    import time as _time
    _time.sleep(0.06)  # let the high-priority arrival come due
    with set_mesh(eng.mesh):
        eng.tick()
    assert hi[0].slot is not None  # the HEAD got the freed slot
    parked = [r for r in low if r.state is RequestState.PARKED]
    assert len(parked) == 1
    # full run still matches the oracle
    while eng._by_slot or eng.scheduler.has_pending:
        with set_mesh(eng.mesh):
            eng.tick()
    flat = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(
        [Request(rid=r.rid, prompt=r.prompt.copy(),
                 max_new_tokens=r.max_new_tokens) for r in low + hi]))
    assert _streams(eng.metrics) == want


def test_park_restore_roundtrip_bookkeeping():
    mem = KVMemoryManager(17, 4)
    mem.admit_slot(0, np.arange(10))
    used_before = mem.pages.n_used
    host = {"k": np.ones((2, 3, 4, 1, 2), np.float32)}
    mem.park(7, 0, host, live_tokens=10, next_tok=42)
    assert mem.pages.n_used == 0 and mem.n_parked == 1
    assert mem.park_bytes == host["k"].nbytes
    with pytest.raises(PageError):
        mem.park(7, 0, host, 1, 1)  # double park of the same rid
    plan = mem.restore(7, 3)
    assert plan.seq.next_tok == 42 and plan.seq.live_tokens == 10
    assert len(plan.table) == 3 == used_before
    # the donor slot was freed at park, so nothing re-shares here: every
    # page must be written and the full payload counts as moved
    assert plan.shared_pages == 0
    assert plan.write_ids == plan.table
    mem.check({3: 10})
    assert mem.n_parked == 0 and mem.restore_bytes == mem.park_bytes


def test_memory_fuzz_selftest():
    _selftest(seed=7, steps=800)


# ---------------------------------------------------------------------------
# Engine: sharing on/off — identical streams, fewer pages/bytes
# ---------------------------------------------------------------------------


def test_shared_header_streams_match_flat_oracle(cfg):
    flat = ServeEngine(cfg, capacity=8, cache_len=64, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(_shared_burst(cfg)))
    arms = {}
    for share in (False, True):
        eng = ServeEngine(cfg, capacity=8, cache_len=64, prefill_bucket=8,
                          n_workers=1, seed=0, kv_layout="paged",
                          chunked_prefill=False, prefix_share=share,
                          debug_checks=True)
        m = eng.run(_shared_burst(cfg))
        assert _streams(m) == want
        assert eng.pages.n_used == 0  # every page returned
        arms[share] = m.summarize()
    s_on, s_off = arms[True], arms[False]
    assert s_on["shared_page_hits_total"] > 0
    assert s_off["shared_page_hits_total"] == 0
    # sharing moves fewer admission bytes and holds fewer physical pages
    assert s_on["admission_bytes_total"] < s_off["admission_bytes_total"]
    assert s_on["page_occupancy_mean"] < s_off["page_occupancy_mean"]
    assert s_on["shared_extra_pages_mean"] > 0


def test_cow_break_preserves_streams(cfg):
    """Identical prompts with a partial last page: every sharer's first
    decode write breaks the share; streams must still match the oracle."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    reqs = lambda: [Request(rid=i, prompt=prompt.copy(), max_new_tokens=5)  # noqa: E731
                    for i in range(3)]
    flat = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(reqs()))
    eng = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    m = eng.run(reqs())
    assert _streams(m) == want
    s = m.summarize()
    assert s["cow_breaks_total"] >= 2
    assert eng.pages.n_used == 0


def test_chunked_prefill_skips_shared_pages(cfg):
    """Chunked admissions start prefill AFTER the shared full pages: fewer
    chunks, same tokens."""
    mk = lambda: _shared_burst(cfg, n=4, header=24, suffix=(8, 12),  # noqa: E731
                               max_new=(3, 4), seed=5,
                               arrivals=np.array([0.0, 0.05, 0.1, 0.15]))
    kw = dict(capacity=4, cache_len=64, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged", prefill_chunk=8, debug_checks=True)
    off = ServeEngine(cfg, prefix_share=False, **kw)
    m_off = off.run(mk())
    on = ServeEngine(cfg, prefix_share=True, **kw)
    m_on = on.run(mk())
    assert _streams(m_on) == _streams(m_off)
    s_on, s_off = m_on.summarize(), m_off.summarize()
    assert s_on["prefill_chunks_total"] < s_off["prefill_chunks_total"]
    assert s_on["shared_page_hits_total"] > 0


def test_sharing_across_resize_matches_oracle(cfg):
    flat = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(_shared_burst(cfg, n=6, header=16,
                                           suffix=(4, 8))))
    pol = ElasticScalingPolicy([ScaleEvent(0, 1), ScaleEvent(3, 2),
                                ScaleEvent(7, 1)])
    eng = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, policies=[pol], kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    m = eng.run(_shared_burst(cfg, n=6, header=16, suffix=(4, 8)))
    assert len(m.scale_events) == 2
    assert _streams(m) == want
    # page-granular migration accounting recorded for both scale events
    assert len(m.resize_moves) == 2
    for (_, _, slots_moved, nbytes) in m.resize_moves:
        assert nbytes == 0 or slots_moved > 0


# ---------------------------------------------------------------------------
# Engine: preempt / park / restore
# ---------------------------------------------------------------------------


def _preempt_workload(cfg):
    low = synthetic_requests(2, vocab_size=cfg.vocab_size,
                             arrivals=np.zeros(2), prompt_len=(6, 8),
                             max_new_tokens=(12, 14),
                             rng=np.random.default_rng(2), tenant="lo")
    hi = synthetic_requests(1, vocab_size=cfg.vocab_size,
                            arrivals=np.array([0.01]), prompt_len=(6, 8),
                            max_new_tokens=(4, 4), priority=2,
                            rng=np.random.default_rng(3), tenant="hi",
                            rid_base=100)
    return low + hi


def test_priority_preemption_parks_and_restores_bit_identical(cfg):
    flat = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(_preempt_workload(cfg)))
    eng = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    m = eng.run(_preempt_workload(cfg))
    s = m.summarize()
    assert s["parked_total"] >= 1 and s["restored_total"] >= 1
    assert s["kv_moved_bytes_total"] > 0
    assert _streams(m) == want  # parked streams resume bit-for-bit
    assert s["requests_finished"] == 3
    assert eng.pages.n_used == 0 and eng.mem.n_parked == 0


def test_evict_off_never_parks(cfg):
    eng = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, evict=False, debug_checks=True)
    m = eng.run(_preempt_workload(cfg))
    assert m.summarize()["parked_total"] == 0
    flat = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    assert _streams(m) == _streams(flat.run(_preempt_workload(cfg)))


def test_park_frees_pages_and_preserves_victim_state(cfg):
    eng = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    reqs = synthetic_requests(2, vocab_size=cfg.vocab_size,
                              arrivals=np.zeros(2), prompt_len=(6, 8),
                              max_new_tokens=(10, 10),
                              rng=np.random.default_rng(4))
    eng.submit(reqs)
    eng._now()
    for _ in range(3):
        with set_mesh(eng.mesh):
            eng.tick()
    victim_slot = sorted(eng._by_slot)[0]
    victim = eng._by_slot[victim_slot]
    pages_held = eng.pages.n_pages_of(victim_slot)
    used_before = eng.pages.n_used
    nbytes = eng.park(victim_slot)
    assert nbytes == pages_held * eng._page_bytes  # only live pages moved
    assert eng.pages.n_used == used_before - pages_held
    assert victim.state is RequestState.PARKED and victim.slot is None
    assert eng.mem.n_parked == 1
    # drive to completion: the parked request restores and finishes
    while eng._by_slot or eng.scheduler.has_pending:
        with set_mesh(eng.mesh):
            eng.tick()
    assert victim.state is RequestState.FINISHED
    assert len(victim.generated) == victim.max_new_tokens
    assert eng.pages.n_used == 0 and eng.mem.n_parked == 0


def test_random_park_fuzz_streams_match_oracle(cfg):
    """Seeded fuzz: park a random active slot every few ticks; restores ride
    the normal admission path; token streams must match the flat oracle and
    the refcount/coverage guard must hold every tick."""
    mk = lambda: _shared_burst(cfg, n=6, header=16, suffix=(4, 8),  # noqa: E731
                               max_new=(6, 10), seed=9)
    flat = ServeEngine(cfg, capacity=3, cache_len=48, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(mk()))
    rng = np.random.default_rng(11)
    eng = ServeEngine(cfg, capacity=3, cache_len=48, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, debug_checks=True)
    eng.submit(mk())
    eng._now()
    parked = 0
    for i in range(200):
        if not (eng._by_slot or eng._prefilling
                or eng.scheduler.has_pending):
            break
        if eng._by_slot and rng.random() < 0.25:
            slot = int(rng.choice(sorted(eng._by_slot)))
            eng.park(slot)
            parked += 1
        with set_mesh(eng.mesh):
            eng.tick()
    assert parked > 0
    assert _streams(eng.metrics) == want
    assert eng.pages.n_used == 0 and eng.mem.n_parked == 0


def test_spec_decode_with_sharing_matches_oracle(cfg):
    """Speculative decode + prefix sharing + COW compose: repetitive shared
    prompts, spec on, streams equal the non-spec share-off baseline."""
    mk = lambda: _shared_burst(cfg, n=4, header=12, suffix=(4, 6),  # noqa: E731
                               max_new=(6, 8), seed=13)
    base = ServeEngine(cfg, capacity=4, cache_len=64, prefill_bucket=8,
                       n_workers=1, seed=0, kv_layout="paged",
                       chunked_prefill=False, prefix_share=False)
    want = _streams(base.run(mk()))
    eng = ServeEngine(cfg, capacity=4, cache_len=64, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, spec="ngram", spec_k=3,
                      debug_checks=True)
    m = eng.run(mk())
    assert _streams(m) == want
    assert m.summarize()["shared_page_hits_total"] > 0
    assert eng.pages.n_used == 0


def test_flat_layout_rejects_share_and_evict(cfg):
    with pytest.raises(ValueError, match="prefix_share requires"):
        ServeEngine(cfg, capacity=2, cache_len=16, prefix_share=True)
    with pytest.raises(ValueError, match="evict requires"):
        ServeEngine(cfg, capacity=2, cache_len=16, evict=True)
