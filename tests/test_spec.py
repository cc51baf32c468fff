"""Speculative-decode tests: drafter units, bit-equality of speculative vs
baseline greedy streams (flat + paged layouts, incl. across a mid-run
k 1->2->1 resize and the Pallas verify path), acceptance-rate sanity on
repetitive vs random workloads, rollback invariants after partial rejection
(lengths / block tables / free list), and the batched chunked-prefill
satellite (fewer dispatches, identical tokens)."""
import numpy as np
import pytest
from jax import set_mesh

from repro.configs import get_config, smoke_variant
from repro.core import ElasticScalingPolicy, ScaleEvent
from repro.serve import (DraftModelDrafter, NgramDrafter, Request,
                         ServeEngine, greedy_accept, synthetic_requests)


@pytest.fixture(scope="module")
def cfg():
    return smoke_variant(get_config("smollm-360m"))


def _burst(cfg, n=6, seed=0, prompt=(6, 16), max_new=(5, 12)):
    return synthetic_requests(n, vocab_size=cfg.vocab_size,
                              arrivals=np.zeros(n), prompt_len=prompt,
                              max_new_tokens=max_new,
                              rng=np.random.default_rng(seed))


def _repetitive(cfg, n=6, seed=0, prompt_len=(12, 20), max_new=(4, 7)):
    """Prompts that tile a short random motif (prompt-lookup's best case)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        motif = rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 5)))
        plen = int(rng.integers(*prompt_len))
        prompt = np.tile(motif, -(-plen // len(motif)))[:plen]
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=int(rng.integers(*max_new))))
    return reqs


def _streams(metrics):
    return {r.rid: list(r.generated) for r in metrics.requests}


# ---------------------------------------------------------------------------
# Drafter units + accept rule
# ---------------------------------------------------------------------------


def test_greedy_accept_prefix_rule():
    v = np.array([5, 7, 9, 2, 4])
    assert greedy_accept(np.array([5, 7, 9, 2]), v) == 4
    assert greedy_accept(np.array([5, 7, 1, 2]), v) == 2
    assert greedy_accept(np.array([3]), v) == 0
    assert greedy_accept(np.empty(0, np.int64), v) == 0


def test_ngram_drafter_continues_repetition():
    d = NgramDrafter(max_ngram=3)
    ctx = np.tile([5, 7, 9], 6)  # ... 5 7 9 | next: 5 7 9 5
    (out,) = d.propose([ctx], 4)
    assert out.tolist() == [5, 7, 9, 5]
    # longest-suffix match wins over a shorter, more recent one
    ctx2 = np.array([1, 2, 3, 4, 9, 9, 1, 2, 3])
    (out2,) = d.propose([ctx2], 3)
    assert out2.tolist() == [4, 9, 9]


def test_ngram_drafter_no_match_proposes_nothing():
    d = NgramDrafter()
    (out,) = d.propose([np.arange(32)], 4)  # all-unique context
    assert out.size == 0
    (short,) = d.propose([np.array([3])], 4)  # too short to match
    assert short.size == 0
    assert d.propose([], 4) == []


def test_ngram_drafter_prefers_most_recent_occurrence():
    # pattern [4] occurs twice with different continuations; the most
    # recent one (-> 8) must win over the older one (-> 6)
    ctx = np.array([4, 6, 1, 4, 8, 2, 4])
    d = NgramDrafter(max_ngram=1)
    (out,) = d.propose([ctx], 2)
    assert out.tolist() == [8, 2]


# ---------------------------------------------------------------------------
# Bit-equality: speculative == baseline greedy (the lossless claim)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["flat", "paged"])
def test_spec_matches_baseline_streams(cfg, layout):
    kw = dict(capacity=4, cache_len=32, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout=layout)
    if layout == "paged":
        kw["chunked_prefill"] = False
    want = _streams(ServeEngine(cfg, **kw).run(_burst(cfg)))
    eng = ServeEngine(cfg, spec="ngram", spec_k=3, debug_checks=True, **kw)
    m = eng.run(_burst(cfg))
    assert _streams(m) == want
    s = m.summarize()
    assert s["requests_finished"] == 6
    # verification really batched: fewer dispatches than emitted ticks of
    # the baseline, and drafts were actually accepted
    assert s["spec_accepted_total"] > 0
    if layout == "paged":
        eng.pages.check_invariants()
        assert eng.pages.n_used == 0


@pytest.mark.parametrize("layout", ["flat", "paged"])
def test_spec_matches_baseline_across_resize(cfg, layout):
    """k: 1 -> 2 -> 1 mid-run with speculation on: drafter state and the
    page pool reshard together; streams stay bit-identical."""
    kw = dict(capacity=4, cache_len=32, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout=layout)
    if layout == "paged":
        kw["chunked_prefill"] = False
    want = _streams(ServeEngine(cfg, **kw).run(_burst(cfg)))
    pol = ElasticScalingPolicy([ScaleEvent(0, 1), ScaleEvent(2, 2),
                                ScaleEvent(5, 1)])
    eng = ServeEngine(cfg, spec="ngram", spec_k=2, policies=[pol],
                      debug_checks=True, **kw)
    m = eng.run(_burst(cfg))
    assert len(m.scale_events) == 2, m.scale_events
    assert _streams(m) == want


def test_spec_pallas_impl_matches_baseline(cfg):
    """The Pallas paged kernel scores all k+1 positions in one call
    (q_span > 1) and reproduces the baseline stream."""
    kw = dict(capacity=2, cache_len=16, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged", chunked_prefill=False)
    want = _streams(ServeEngine(cfg, **kw).run(
        _burst(cfg, 3, prompt=(4, 8), max_new=(3, 6))))
    eng = ServeEngine(cfg, spec="ngram", spec_k=2, paged_impl="pallas",
                      debug_checks=True, **kw)
    m = eng.run(_burst(cfg, 3, prompt=(4, 8), max_new=(3, 6)))
    assert _streams(m) == want


def test_pallas_chunked_prefill_matches_xla(cfg):
    """paged_impl='pallas' now drives the chunked-prefill spans through the
    kernel too (q_span = chunk); streams must match the XLA gather path."""
    kw = dict(capacity=2, cache_len=48, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged", prefill_chunk=8)
    reqs = lambda: _burst(cfg, 3, seed=4, prompt=(18, 30),  # noqa: E731
                          max_new=(3, 5))
    want = _streams(ServeEngine(cfg, **kw).run(reqs()))
    eng = ServeEngine(cfg, paged_impl="pallas", debug_checks=True, **kw)
    m = eng.run(reqs())
    assert m.summarize()["prefill_chunks_total"] > 0
    assert _streams(m) == want


def test_draft_model_same_params_accepts_everything(cfg):
    """A draft model with the TARGET's own params drafts the target's own
    greedy stream, so acceptance must be exactly 1.0 — the deterministic
    upper bound (and proof the verify/accept plumbing drops nothing)."""
    kw = dict(capacity=4, cache_len=32, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged", chunked_prefill=False)
    base = ServeEngine(cfg, **kw)
    want = _streams(base.run(_burst(cfg)))
    eng = ServeEngine(cfg, spec="draft", spec_k=3, draft_cfg=cfg,
                      draft_params=base.params, debug_checks=True, **kw)
    m = eng.run(_burst(cfg))
    s = m.summarize()
    assert _streams(m) == want
    assert s["spec_acceptance_rate"] == 1.0
    assert s["tokens_per_dispatch"] > 1.3


def test_spec_with_chunked_prefill(cfg):
    """Speculative decode of in-flight streams interleaves with chunked
    prefill of long prompts without disturbing either."""
    kw = dict(capacity=4, cache_len=48, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged")
    reqs = lambda: _burst(cfg, 4, seed=3, prompt=(18, 30),  # noqa: E731
                          max_new=(3, 5))
    want = _streams(ServeEngine(cfg, chunked_prefill=False,
                                **kw).run(reqs()))
    eng = ServeEngine(cfg, prefill_chunk=8, spec="ngram", spec_k=3,
                      debug_checks=True, **kw)
    m = eng.run(reqs())
    assert m.summarize()["prefill_chunks_total"] > 0
    assert _streams(m) == want


# ---------------------------------------------------------------------------
# Acceptance-rate sanity
# ---------------------------------------------------------------------------


def _next_token_map(cfg, params):
    """g(t): the greedy next token of a window-1 model, whose every
    position attends only itself, so its next token is a function of the
    current token alone."""
    import jax.numpy as jnp
    from repro.models import model as M
    toks = jnp.arange(cfg.vocab_size, dtype=jnp.int32)[:, None]
    logits, _ = M.forward(cfg, params, toks, rules=None, remat=False)
    return np.asarray(jnp.argmax(logits[:, -1], -1))


def _cycle(nxt):
    """A cycle of the map t -> nxt[t] (distinct tokens, nxt[c[-1]] = c[0])."""
    seen, t = {}, 0
    while t not in seen:
        seen[t] = len(seen)
        t = int(nxt[t])
    order = sorted(seen, key=seen.get)
    return order[seen[t]:]


def _misleading(nxt, n, seed, prompt_len=(12, 19), max_new=(4, 7)):
    """Random prompts whose own history misleads the drafter: the model's
    first token x = nxt[last] occurs once earlier in the prompt, followed
    by a token other than nxt[x], so the first draft is rejected."""
    V = len(nxt)
    rng = np.random.default_rng(seed)
    reqs = []
    while len(reqs) < n:
        plen = int(rng.integers(*prompt_len))
        prompt = rng.integers(0, V, size=plen)
        last = int(prompt[-1])
        x = int(nxt[last])
        j = int(rng.integers(0, plen - 2))
        prompt[j] = x
        rest = np.delete(np.arange(plen), [j, plen - 1])
        if (x == last or int(prompt[j + 1]) == int(nxt[x])
                or np.isin(prompt[rest], [x, last]).any()):
            continue
        reqs.append(Request(rid=len(reqs), prompt=prompt.astype(np.int32),
                            max_new_tokens=int(rng.integers(*max_new))))
    return reqs


def test_acceptance_repetitive_beats_random(cfg):
    """Prompt-lookup drafting on a model whose next token depends on the
    current token alone (attention window 1): prompts that tile a cycle of
    that map are continued by the model exactly as the drafter proposes,
    so every draft is accepted; prompts whose history misleads the drafter
    reject their first draft.  Repetitive prompts therefore accept strictly
    more, by construction."""
    import dataclasses

    import jax
    from repro.models import model as M
    cfg1 = dataclasses.replace(cfg, sliding_window=1)
    params = M.init_params(cfg1, jax.random.key(0))
    nxt = _next_token_map(cfg1, params)
    cyc = np.asarray(_cycle(nxt), np.int32)
    rng = np.random.default_rng(1)
    rep = []
    for i in range(6):
        plen = int(rng.integers(12, 20))
        tiled = np.tile(np.roll(cyc, -i), -(-plen // len(cyc)) + 1)[:plen]
        rep.append(Request(rid=i, prompt=tiled,
                           max_new_tokens=int(rng.integers(4, 7))))
    accs = {}
    for name, reqs in (("rep", rep), ("rand", _misleading(nxt, 6, seed=1))):
        eng = ServeEngine(cfg1, capacity=8, cache_len=64, prefill_bucket=16,
                          n_workers=1, seed=0, params=params,
                          kv_layout="paged", spec="ngram", spec_k=4,
                          debug_checks=True)
        m = eng.run(reqs)
        for r in m.requests:  # the window-1 model follows its map exactly
            ctx = list(r.prompt) + r.generated
            assert all(int(nxt[a]) == b for a, b in
                       zip(ctx[r.prompt_len - 1:], r.generated)), r.rid
        accs[name] = m.summarize()["spec_acceptance_rate"]
    assert accs["rep"] == 1.0, accs
    assert accs["rand"] < 1.0, accs
    assert accs["rep"] > accs["rand"], accs


def test_spec_raises_tokens_per_dispatch(cfg):
    """The payoff metric: >= 1.3x tokens per decode dispatch at equal
    output on the repetitive workload (the acceptance-criteria floor)."""
    out = {}
    for mode in ("off", "ngram"):
        eng = ServeEngine(cfg, capacity=8, cache_len=64, prefill_bucket=16,
                          n_workers=1, seed=0, kv_layout="paged",
                          spec=mode, spec_k=4)
        m = eng.run(_repetitive(cfg, n=8, seed=1, max_new=(16, 28)))
        out[mode] = (_streams(m), m.summarize()["tokens_per_dispatch"])
    assert out["ngram"][0] == out["off"][0]  # equal output, fewer dispatches
    assert out["ngram"][1] / out["off"][1] >= 1.3, out


# ---------------------------------------------------------------------------
# Rollback invariants (lengths / block tables / free list)
# ---------------------------------------------------------------------------


def test_rollback_frees_rejected_draft_pages(cfg):
    """Partial rejection with page_size 4 and k 4 crosses page boundaries:
    after every tick each live slot must hold EXACTLY the pages its live
    tokens need (pages.check(live) inside debug_checks), positions never
    exceed live KV, and the run ends with an empty owner map."""
    eng = ServeEngine(cfg, capacity=4, cache_len=32, prefill_bucket=8,
                      page_size=4, n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, spec="ngram", spec_k=4,
                      debug_checks=True)
    eng.submit(_burst(cfg, 6, seed=2, prompt=(6, 12), max_new=(6, 12)))
    eng._now()
    saw_rejection = False
    while eng._by_slot or eng.scheduler.has_pending or eng._prefilling:
        with set_mesh(eng.mesh):
            rec = eng.tick()  # debug_checks validates tables per tick
        if rec.spec_drafted > rec.spec_accepted:
            saw_rejection = True
        for slot in eng._by_slot:
            assert eng.pages.n_pages_of(slot) == eng.pages.pages_for(
                int(eng.scheduler.pool.pos[slot]))
    assert saw_rejection, "workload never exercised a rejected draft"
    eng.pages.check_invariants()
    assert eng.pages.n_used == 0
    assert eng.scheduler.pool.n_used == 0


def test_pages_trim():
    from repro.serve import PageAllocator, PageError
    pa = PageAllocator(n_pages=9, page_size=4)
    t = pa.alloc_slot(0, 15)  # 4 pages
    freed = pa.trim(0, 6)  # keep 2
    assert freed == t[2:] and pa.n_pages_of(0) == 2
    assert pa.trim(0, 6) == []  # idempotent
    pa.check({0: 6})
    with pytest.raises(PageError):
        pa.check({0: 3})  # over-coverage now detected
    with pytest.raises(PageError):
        pa.trim(1, 0)  # no table
    # trimmed pages are immediately reusable
    pa.alloc_slot(1, 8 * 4 - 2 * 4)  # rest of the pool
    pa.check_invariants()


def test_spec_at_kv_capacity_finishes_cleanly(cfg):
    """A slot at the KV boundary degrades its draft budget to fit, finishes
    instead of overwriting, and returns every page."""
    eng = ServeEngine(cfg, capacity=2, cache_len=16, prefill_bucket=8,
                      n_workers=1, seed=0, kv_layout="paged",
                      chunked_prefill=False, spec="ngram", spec_k=4,
                      debug_checks=True)
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
    assert len(r.generated) == eng.cache_len - r.prompt_len + 1
    eng.pages.check_invariants()
    assert eng.pages.n_used == 0


# ---------------------------------------------------------------------------
# Batched chunked prefill (satellite)
# ---------------------------------------------------------------------------


def test_batched_chunked_prefill_fewer_dispatches_same_streams(cfg):
    """Several long prompts mid-prefill on the same tick share one batched
    chunk forward per table-width bucket; tokens are unchanged."""
    kw = dict(capacity=4, cache_len=48, prefill_bucket=8, n_workers=1,
              seed=0, kv_layout="paged")
    reqs = lambda: _burst(cfg, 4, seed=3, prompt=(18, 30),  # noqa: E731
                          max_new=(3, 5))
    want = _streams(ServeEngine(cfg, chunked_prefill=False,
                                **kw).run(reqs()))
    eng = ServeEngine(cfg, prefill_chunk=8, debug_checks=True, **kw)
    m = eng.run(reqs())
    s = m.summarize()
    assert _streams(m) == want
    assert s["prefill_chunks_total"] > 0
    # the batching claim: strictly fewer forwards than chunks
    assert s["prefill_dispatches_total"] < s["prefill_chunks_total"], s
