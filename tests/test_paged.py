"""Paged-KV subsystem tests: page-allocator invariants, Pallas kernel
parity, paged-vs-flat token-stream bit-equality (incl. across elastic
resize), chunked prefill interleaving, O(pages) admission accounting,
at-capacity finish (pos-clamp regression), and jit-cache bounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import set_mesh

from repro.configs import get_config, smoke_variant
from repro.core import ElasticScalingPolicy, ScaleEvent
from repro.kernels import ref
from repro.kernels.paged_attention import (_block_sizes, _exact_parts,
                                           _fetch_plan,
                                           paged_attention,
                                           random_case)
from repro.serve import (PageAllocator, PageError, ServeEngine,
                         synthetic_requests)
from repro.serve.engine import _lru_get


@pytest.fixture(scope="module")
def cfg():
    return smoke_variant(get_config("smollm-360m"))


def _burst(cfg, n=8, seed=0, prompt=(6, 16), max_new=(5, 9)):
    return synthetic_requests(n, vocab_size=cfg.vocab_size,
                              arrivals=np.zeros(n), prompt_len=prompt,
                              max_new_tokens=max_new,
                              rng=np.random.default_rng(seed))


def _streams(metrics):
    return {r.rid: list(r.generated) for r in metrics.requests}


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------


def test_page_allocator_basic():
    pa = PageAllocator(n_pages=9, page_size=8)  # 8 usable + null
    assert pa.pages_for(0) == 0 and pa.pages_for(1) == 1
    assert pa.pages_for(8) == 1 and pa.pages_for(9) == 2
    t0 = pa.alloc_slot(0, 17)  # 3 pages
    assert len(t0) == 3 and 0 not in t0  # null page never handed out
    with pytest.raises(PageError):
        pa.alloc_slot(0)  # double table
    added = pa.ensure(0, 20)
    assert added == [] and pa.n_pages_of(0) == 3
    added = pa.ensure(0, 25)
    assert len(added) == 1 and pa.n_pages_of(0) == 4
    pa.alloc_slot(1, 30)  # 4 more pages -> pool exhausted
    with pytest.raises(PageError):
        pa.ensure(1, 40)
    pa.check_invariants()
    freed = pa.free_slot(0)
    assert sorted(freed) == sorted(t0 + added)
    with pytest.raises(PageError):
        pa.free_slot(0)  # double free
    pa.check_invariants()
    assert pa.n_used == 4 and 0 < pa.occupancy() < 1


def test_page_allocator_random_churn():
    rng = np.random.default_rng(0)
    pa = PageAllocator(n_pages=33, page_size=4)
    held = {}
    for i in range(300):
        if held and (rng.random() < 0.4 or pa.n_free < 8):
            slot = rng.choice(list(held))
            pa.free_slot(slot)
            del held[slot]
        else:
            slot = i
            pa.alloc_slot(slot, int(rng.integers(1, 17)))
            held[slot] = True
            if rng.random() < 0.5:
                pa.ensure(slot, int(rng.integers(1, 25)))
        pa.check_invariants()
    # every live table reachable through table_array, no overlaps
    width = pa.max_table_len()
    if held:
        arr = pa.table_array(max(held) + 1, width, only=list(held))
        live = arr[arr >= 0]
        assert len(live) == len(set(live.tolist())) == pa.n_used


def test_page_allocator_defrag():
    pa = PageAllocator(n_pages=17, page_size=8)
    for s in range(4):
        pa.alloc_slot(s, 24)  # 3 pages each -> 12 pages... exhausts at s=4
    pa.free_slot(1)
    pa.free_slot(2)
    before = {s: pa.table(s) for s in (0, 3)}
    src = pa.defrag()
    assert src is not None
    pa.check_invariants()
    # compact: live pages now occupy ids 1..n_used contiguously
    live = sorted(p for s in (0, 3) for p in pa.table(s))
    assert live == list(range(1, pa.n_used + 1))
    # src is the gather map: new_pool[i] = old_pool[src[i]]
    for s in (0, 3):
        for new_pg, old_pg in zip(pa.table(s), before[s]):
            assert src[new_pg] == old_pg
    assert pa.defrag() is None  # already compact


def test_table_array_only_and_width_checks():
    pa = PageAllocator(n_pages=9, page_size=8)
    pa.alloc_slot(0, 30)  # 4 pages
    pa.alloc_slot(2, 6)  # 1 page
    arr = pa.table_array(4, 4)
    assert (arr[1] == -1).all() and (arr[3] == -1).all()
    assert (arr[0] >= 0).all() and (arr[2, 0] >= 0) and (arr[2, 1:] == -1).all()
    # restricting to slot 2 lets the width shrink below slot 0's table
    only = pa.table_array(4, 1, only=[2])
    assert only[2, 0] == arr[2, 0] and (only[0] == -1).all()
    with pytest.raises(PageError):
        pa.table_array(4, 2)  # slot 0 table would truncate
    with pytest.raises(PageError):
        pa.table_array(4, 4, only=[1])  # no table for slot 1


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode) vs pure-jnp oracle
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # (B, KV, G, hd, ps, P, window, q_span[, lengths])
    (3, 2, 4, 32, 8, 4, 0, 1),
    (2, 1, 8, 64, 16, 3, 0, 1),
    (4, 2, 2, 32, 8, 8, 0, 1),
    (3, 2, 4, 32, 8, 6, 16, 1),  # sliding window
    (3, 2, 4, 32, 8, 4, 0, 3),  # Q>1: speculative verify spans
    (2, 1, 8, 64, 16, 3, 0, 5),
    (3, 2, 2, 32, 8, 6, 16, 4),  # Q>1 + sliding window
    # three or more blocks of 8 pages of 64 tokens; row 0 has length 0
    (3, 2, 4, 32, 64, 26, 0, 1, (0, 1100, 1024)),  # mid-block; on a boundary
    (3, 2, 4, 32, 64, 26, 0, 1, (0, 1536, 513)),  # on the width's boundary
    (3, 2, 4, 32, 64, 26, 100, 1, (0, 1600, 700)),  # window < a block
    (3, 2, 2, 32, 64, 30, 0, 4, (0, 1026, 1537)),  # spans cross a boundary
    (3, 2, 2, 32, 64, 27, 150, 5, (0, 514, 1700)),  # spans + window
    (2, 2, 4, 32, 64, 8, 0, 130, (0, 400)),  # a chunk: one page a step
    # danube's heads (8 x 80 lanes, no lane multiple each) and mellum's
    # (4 x 128), over two blocks of 32 pages
    (3, 8, 4, 80, 16, 40, 0, 1, (0, 333, 640)),
    (3, 4, 8, 128, 16, 40, 200, 1, (0, 517, 640)),
    (3, 8, 4, 80, 16, 40, 100, 3, (0, 512, 600)),
    # a chunk whose rows take four steps
    (2, 2, 4, 64, 64, 12, 0, 520, (0, 600)),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_attention_kernel_parity(case):
    """Interpret-mode kernel vs the oracle, reading layer 1 of a 3-layer
    stack of pools."""
    B, KV, G, hd, ps, P, window, Q, *fixed = case
    rows, ppb = _block_sizes(ps, KV * Q * G, KV * hd, P)
    if fixed and hd == 32:  # the multi-block cases must stay multi-block
        assert -(-P // ppb) >= 3
    if Q == 130:
        assert ppb == 1
    if Q == 520:
        assert KV * Q * G // rows == 4
    q, kp, vp, table, lengths = random_case(case, np.random.default_rng(1),
                                            layers=3)
    lengths = lengths.at[0].set(0)  # inactive row must return zeros
    out = paged_attention(q, kp, vp, 1, table, lengths, window=window,
                          q_span=Q, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, 1, table, lengths,
                                   window=window, q_span=Q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out)[0] == 0.0)


def test_paged_attention_reads_only_its_layer():
    """Layer 0 of an L = 1 stack reads what the same pages as layer 2 of a
    stack of 3 read; the other layers' pages change nothing."""
    case = (2, 8, 4, 80, 16, 6, 0, 1, (40, 90))
    q, kp, vp, table, lengths = random_case(case, np.random.default_rng(4),
                                            layers=3)
    out = paged_attention(q, kp, vp, 2, table, lengths, interpret=True)
    one = paged_attention(q, kp[2:], vp[2:], 0, table, lengths,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))
    other = paged_attention(q, kp, vp, 0, table, lengths, interpret=True)
    assert not np.allclose(np.asarray(other), np.asarray(out))


def test_fetch_plan_reads_only_owned_pages_and_repeats_past_the_tail():
    """Operand i fetches the table's page while it is live and then repeats
    its own last live page (no new DMA); no row reads a page it does not
    own, and a row with no live page reads the null page 0."""
    ps, ppb = 4, 3
    table = np.array([[5, 9, 2, 7, 11, 4, -1, -1],  # 6 pages of 8
                      [3, -1, -1, -1, -1, -1, -1, -1],  # 1 page
                      [-1] * 8], np.int32)  # length 0
    lengths = np.array([23, 2, 0], np.int32)
    plan = np.asarray(_fetch_plan(jnp.asarray(table), jnp.asarray(lengths),
                                  ps, ppb))
    assert plan.shape == (3, 9)  # ceil(8 / 3) blocks of 3 operands
    np.testing.assert_array_equal(plan[0], [5, 9, 2, 7, 11, 4, 7, 11, 4])
    np.testing.assert_array_equal(plan[1], [3, 3, 3, 3, 3, 3, 3, 3, 3])
    np.testing.assert_array_equal(plan[2], [0] * 9)


def test_exact_parts_sum_back_to_float32():
    """The bfloat16 terms of P sum back to P exactly, so P.V through them is
    P.V in float32."""
    p = jax.random.uniform(jax.random.PRNGKey(0), (64, 512), jnp.float32)
    p = p * jnp.exp(-30.0 * p)  # softmax-like spread of magnitudes
    parts = _exact_parts(p, jnp.bfloat16)
    assert all(x.dtype == jnp.bfloat16 for x in parts)
    total = sum(x.astype(jnp.float32) for x in parts)
    np.testing.assert_array_equal(np.asarray(total), np.asarray(p))


def test_paged_attention_kernel_bf16_pool():
    """A bfloat16 pool over three blocks matches the float32 oracle to the
    output's own rounding."""
    P = 3 * _block_sizes(16, 32, 640, 1 << 10)[1] - 2  # danube's, Q=1
    case = (2, 8, 4, 80, 16, P, 0, 1, (300, 16 * P - 5))
    q, kp, vp, table, lengths = random_case(case, np.random.default_rng(2),
                                            jnp.bfloat16)
    out = paged_attention(q, kp, vp, 0, table, lengths, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(*(x.astype(jnp.float32)
                                         for x in (q, kp, vp)), 0,
                                       table, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=8e-3, atol=8e-3)


def test_paged_attention_q_span_matches_sequential_refs():
    """A Q-span oracle call must equal Q independent single-query calls at
    the span's successive positions (the verification-correctness core)."""
    rng = np.random.default_rng(3)
    B, KV, G, hd, ps, P, Q = 2, 2, 3, 16, 4, 6, 3
    N = B * P + 1
    q = jnp.asarray(rng.standard_normal((B, KV, Q * G, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((1, N, ps, KV * hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((1, N, ps, KV * hd)), jnp.float32)
    lengths = np.array([Q + 5, P * ps], np.int32)
    table = np.full((B, P), -1, np.int32)
    perm = rng.permutation(np.arange(1, N))
    used = 0
    for b in range(B):
        n = -(-int(lengths[b]) // ps)
        table[b, :n] = perm[used: used + n]
        used += n
    span = ref.paged_attention_ref(q, kp, vp, 0, jnp.asarray(table),
                                   jnp.asarray(lengths), q_span=Q)
    for j in range(Q):
        qj = q.reshape(B, KV, Q, G, hd)[:, :, j]
        lj = jnp.asarray(lengths - (Q - 1 - j), jnp.int32)
        one = ref.paged_attention_ref(qj, kp, vp, 0, jnp.asarray(table),
                                      lj)
        np.testing.assert_allclose(
            np.asarray(span.reshape(B, KV, Q, G, hd)[:, :, j]),
            np.asarray(one), rtol=2e-5, atol=2e-5)


def test_paged_engine_pallas_impl_matches_xla(cfg):
    """The Pallas decode path (interpret mode on CPU) generates the same
    token streams as the XLA gather path."""
    ref_eng = ServeEngine(cfg, capacity=2, cache_len=16, prefill_bucket=8,
                          n_workers=1, seed=0, kv_layout="paged",
                          chunked_prefill=False)
    want = _streams(ref_eng.run(_burst(cfg, 3, prompt=(4, 8),
                                       max_new=(3, 5))))
    eng = ServeEngine(cfg, capacity=2, cache_len=16, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, paged_impl="pallas")
    got = _streams(eng.run(_burst(cfg, 3, prompt=(4, 8), max_new=(3, 5))))
    assert got == want


# ---------------------------------------------------------------------------
# Paged engine == flat engine (the bit-equality oracle)
# ---------------------------------------------------------------------------


def test_paged_vs_flat_identical_streams(cfg):
    flat = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(_burst(cfg)))
    paged = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                        n_workers=1, seed=0, kv_layout="paged",
                        chunked_prefill=False)
    m = paged.run(_burst(cfg))
    assert _streams(m) == want
    assert m.summarize()["requests_finished"] == 8
    paged.pages.check_invariants()
    assert paged.pages.n_used == 0  # every page returned


def test_paged_vs_flat_across_resize(cfg):
    """k: 1 -> 2 -> 1 mid-run on the PAGED pool must match the flat
    baseline token-for-token (pages survive the reshard)."""
    flat = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(_burst(cfg)))
    pol = ElasticScalingPolicy([ScaleEvent(0, 1), ScaleEvent(3, 2),
                                ScaleEvent(7, 1)])
    paged = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                        n_workers=1, seed=0, policies=[pol],
                        kv_layout="paged", chunked_prefill=False)
    m = paged.run(_burst(cfg))
    assert len(m.scale_events) == 2, m.scale_events
    assert _streams(m) == want
    assert m.summarize()["requests_finished"] == 8


def test_defrag_mid_prefill_with_shared_pages(cfg):
    """Defrag while slots are MID-PREFILL and pages are shared: a shared
    page sits in several block tables, so defrag must emit it exactly once
    and remap every table + the prefix index (the old single-owner defrag
    duplicated it, corrupting the gather map).  The leak guard must pass
    immediately after the move and streams must match the no-defrag run."""
    rng = np.random.default_rng(7)
    head = rng.integers(0, cfg.vocab_size, size=16)
    mk = lambda: synthetic_requests(  # noqa: E731
        4, vocab_size=cfg.vocab_size,
        arrivals=np.array([0.0, 0.02, 0.3, 0.32]), prompt_len=(18, 24),
        max_new_tokens=(3, 5), shared_prefix=head,
        rng=np.random.default_rng(8))
    kw = dict(capacity=4, cache_len=64, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged", prefill_chunk=8, debug_checks=True)
    ref_eng = ServeEngine(cfg, **kw)
    want = _streams(ref_eng.run(mk()))
    eng = ServeEngine(cfg, **kw)
    eng.submit(mk())
    eng._now()
    defragged_mid_prefill = 0
    for _ in range(200):
        if not (eng._by_slot or eng._prefilling
                or eng.scheduler.has_pending):
            break
        with set_mesh(eng.mesh):
            eng.tick()
        if eng._prefilling:  # the satellite case: defrag DURING a prefill
            if eng.defrag():
                defragged_mid_prefill += 1
            live = {s: int(eng.scheduler.pool.pos[s]) for s in eng._by_slot}
            live.update({s: off for s, (_, off) in eng._prefilling.items()})
            eng.mem.check(live)
    assert defragged_mid_prefill > 0, "no defrag ran while mid-prefill"
    assert _streams(eng.metrics) == want
    assert eng.mem.stats()["shared_page_hits"] > 0  # sharing was in play
    assert eng.pages.n_used == 0


def test_defrag_mid_run_preserves_streams(cfg):
    flat = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                       n_workers=1, seed=0)
    want = _streams(flat.run(_burst(cfg)))
    eng = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False)
    eng.submit(_burst(cfg))
    eng._now()
    for i in range(12):
        if not (eng._by_slot or eng.scheduler.has_pending):
            break
        with set_mesh(eng.mesh):
            eng.tick()
        if i in (2, 5):
            eng.defrag()
            eng.pages.check_invariants()
    while eng._by_slot or eng.scheduler.has_pending:
        with set_mesh(eng.mesh):
            eng.tick()
    assert _streams(eng.metrics) == want


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_interleaves_with_decode(cfg):
    """Decode of in-flight short requests keeps emitting tokens on the same
    ticks a long prompt is mid-prefill (no whole-prompt stall)."""
    short = _burst(cfg, 3, seed=2, prompt=(4, 6), max_new=(8, 10))
    long_ = synthetic_requests(
        1, vocab_size=cfg.vocab_size, arrivals=np.array([0.02]),
        prompt_len=(24, 24), max_new_tokens=(4, 4),
        rng=np.random.default_rng(3), rid_base=100)
    eng = ServeEngine(cfg, capacity=4, cache_len=40, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      prefill_chunk=8)
    m = eng.run(short + long_)
    s = m.summarize()
    assert s["requests_finished"] == 4
    # the 24-token prompt took 3 chunks over 3 ticks
    assert s["prefill_chunks_total"] >= 3
    interleaved = [t for t in m.ticks if t.prefill_chunks and t.tokens_emitted]
    assert interleaved, "no tick advanced a prefill chunk AND decoded"
    for r in m.requests:
        assert len(r.generated) == r.max_new_tokens


def test_chunked_prefill_matches_unchunked_streams(cfg):
    """Chunking changes WHEN prefill work happens, not the tokens: the same
    workload with chunking on and off generates identical streams."""
    kw = dict(capacity=2, cache_len=48, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged")
    reqs = lambda: _burst(cfg, 3, seed=4, prompt=(18, 30), max_new=(3, 5))  # noqa: E731
    plain = ServeEngine(cfg, chunked_prefill=False, **kw)
    want = _streams(plain.run(reqs()))
    chunked = ServeEngine(cfg, prefill_chunk=8, **kw)
    m = chunked.run(reqs())
    assert m.summarize()["prefill_chunks_total"] > 0
    assert _streams(m) == want


def test_chunked_requires_paged(cfg):
    with pytest.raises(ValueError, match="chunked_prefill requires"):
        ServeEngine(cfg, capacity=2, cache_len=16, kv_layout="flat",
                    chunked_prefill=True)


# ---------------------------------------------------------------------------
# Admission transfer accounting (no full-pool copy)
# ---------------------------------------------------------------------------


def test_paged_admission_bytes_are_page_proportional(cfg):
    reqs = lambda: _burst(cfg, 6, seed=5, prompt=(6, 10), max_new=(2, 3))  # noqa: E731
    flat = ServeEngine(cfg, capacity=8, cache_len=64, prefill_bucket=8,
                       n_workers=1, seed=0)
    fb = flat.run(reqs()).summarize()["admission_bytes_total"]
    paged = ServeEngine(cfg, capacity=8, cache_len=64, prefill_bucket=8,
                        n_workers=1, seed=0, kv_layout="paged",
                        chunked_prefill=False)
    m = paged.run(reqs())
    pb = m.summarize()["admission_bytes_total"]
    # paged admission moved exactly the admitted pages
    pages_written = sum(paged.pages.pages_for(r.prompt_len)
                        for r in m.requests)
    assert pb == pages_written * paged._page_bytes
    # flat rewrites the whole pool per admission group; paged is a fraction
    assert pb < fb / 4, (pb, fb)


def test_page_bytes_count_the_lane_dense_pool(cfg):
    """The pools are (L, N, page_size, KV*hd); a page's K+V bytes across
    the stack are what one page of both pools holds, the same count as
    the (L, N, page_size, KV, hd) layout they replace."""
    eng = ServeEngine(cfg, capacity=2, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged", page_size=8)
    L, KV, hd = cfg.num_layers, cfg.kv_heads(), cfg.head_dim_()
    n_pages = 2 * 32 // 8 + 1
    for pool in eng.blocks.values():
        assert pool.shape == (L, n_pages, 8, KV * hd)
    item = jnp.dtype(cfg.dtype).itemsize
    assert eng._page_bytes == 2 * L * 8 * KV * hd * item
    assert eng._page_bytes * n_pages == eng._pool_bytes


# ---------------------------------------------------------------------------
# At-capacity finish (pos-clamp regression) — both layouts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["flat", "paged"])
def test_slot_at_kv_capacity_finishes_instead_of_overwriting(cfg, layout):
    """Pre-PR3 the decode position was silently clamped to cache_len-1,
    overwriting the last KV row forever.  A request that (bypassing the
    submit guard) would outgrow its KV now finishes early and releases its
    slot; nothing is clamped or overwritten."""
    eng = ServeEngine(cfg, capacity=2, cache_len=16, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout=layout,
                      chunked_prefill=False)
    reqs = _burst(cfg, 1, seed=6, prompt=(8, 8), max_new=(64, 64))
    eng.scheduler.submit(reqs[0])  # around submit()'s up-front reject
    eng.metrics.requests.append(reqs[0])
    eng._now()
    for _ in range(32):
        with set_mesh(eng.mesh):
            eng.tick()
        assert eng.scheduler.pool.pos.max() <= eng.cache_len
        if not eng._by_slot:
            break
    r = reqs[0]
    assert r.state.value == "finished"
    # prompt rows 0..7; decode writes rows 8..15 emitting one token each,
    # plus prefill's first token (whose KV is written by the first decode)
    assert len(r.generated) == eng.cache_len - r.prompt_len + 1
    assert eng.scheduler.pool.n_used == 0
    if layout == "paged":
        eng.pages.check_invariants()
        assert eng.pages.n_used == 0


def test_engine_rejects_oversized_request_still(cfg):
    eng = ServeEngine(cfg, capacity=2, cache_len=16, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged")
    reqs = _burst(cfg, 1, seed=6, prompt=(14, 14), max_new=(8, 8))
    with pytest.raises(ValueError, match="exceeds cache_len"):
        eng.run(reqs)


# ---------------------------------------------------------------------------
# Bounded jit caches
# ---------------------------------------------------------------------------


def test_lru_get_bounds_and_moves_to_end():
    c = {}
    for i in range(5):
        _lru_get(c, i, lambda i=i: i * 10, cap=3)
    assert list(c) == [2, 3, 4]
    _lru_get(c, 2, lambda: None, cap=3)  # hit: moves to end, no rebuild
    assert list(c) == [3, 4, 2] and c[2] == 20
    _lru_get(c, 9, lambda: 90, cap=3)
    assert list(c) == [4, 2, 9]


def test_prefill_cache_bounded_and_exposed(cfg):
    eng = ServeEngine(cfg, capacity=4, cache_len=64, prefill_bucket=8,
                      n_workers=1, seed=0, max_cached_fns=2)
    # prompts spanning 4 distinct buckets (8, 16, 24, 32)
    for plen in (6, 14, 22, 30):
        reqs = synthetic_requests(
            1, vocab_size=cfg.vocab_size, arrivals=np.zeros(1),
            prompt_len=(plen, plen), max_new_tokens=(1, 1),
            rng=np.random.default_rng(plen), rid_base=plen)
        eng.submit(reqs)
        while eng.scheduler.has_pending or eng._by_slot:
            with set_mesh(eng.mesh):
                eng.tick()
    sizes = eng.metrics.summarize()["jit_cache_sizes"]
    assert sizes["prefill_cache"] <= 2
    assert set(sizes) == {"k_cache", "prefill_cache", "insert_cache",
                          "chunk_cache", "restore_cache"}


def test_resize_evicts_stale_mesh_dependents(cfg):
    eng = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                      n_workers=1, seed=0, max_cached_meshes=1)
    # plant a compiled artifact for a mesh key that is about to be evicted
    eng._k_cache[99] = eng._k_cache[1]
    eng._prefill_cache[(99, 8)] = "stale"
    eng._insert_cache[(99, 1, 8)] = "stale"
    eng._chunk_cache[(99, 8, 2)] = "stale"
    eng._restore_cache[(99, 4)] = "stale"
    eng.resize(2)  # single CPU device: km stays 1, 99 falls off the LRU
    assert 99 not in eng._k_cache
    assert not any(k[0] == 99 for k in eng._prefill_cache)
    assert not any(k[0] == 99 for k in eng._insert_cache)
    assert not any(k[0] == 99 for k in eng._chunk_cache)
    assert not any(k[0] == 99 for k in eng._restore_cache)
