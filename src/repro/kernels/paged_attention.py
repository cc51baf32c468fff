"""Paged-attention decode Pallas TPU kernel (block-table gather, O(live)).

One QUERY SPAN per sequence (Q=1 plain decode; Q=k+1 speculative
verification, where the span is [current token, k draft tokens]) attends a
KV cache scattered across fixed-size physical pages.  The block table and
per-sequence lengths/query-start positions are SCALAR-PREFETCHED
(`pltpu.PrefetchScalarGridSpec`) so the k/v BlockSpec index_maps can chase
them: grid step (b, p) DMAs exactly the physical page backing sequence b's
p-th logical page — the kernel never touches pages the sequence does not
own.  Pages past a sequence's live length are clamped to the last live
page in the index_map (a repeated block index, so the pipeline skips the
re-DMA) and their compute is skipped with `pl.when`: per-sequence work is
O(live tokens), not O(pool capacity).

Head layout is grouped-GQA like kernels/flash_attention.py: q is
(B, KV, Q*G, hd) with the G query heads of kv head `kv` contracting against
the COMPACT page pool (no head-expansion gather, 1x kv-page traffic).  The
Q query positions of a span ride along the row dim — row r is query
position r // G at absolute position q_start[b] + r // G, and each row
carries its own causal/sliding-window mask, so verifying k drafts costs ONE
page sweep instead of k+1.  Online-softmax state (acc/m/l per kv head)
lives in VMEM scratch across the page steps, which form the innermost
(sequential) grid dimension.

Block shapes: a k/v block is one WHOLE page with all its kv heads,
(page_size, KV, hd), and the kernel loops over the KV heads inside.  The
TPU compiler tiles the last two block dims, which must be multiples of
(8, 128) or span the array: (KV, hd) spans the pool's (N, page_size, KV, hd)
layout at any head count, page size and dtype, where a one-head block
(page_size, 1, hd) would not.  q/out blocks are (KV, Q*G, hd) per sequence.
Correctness is validated on CPU in interpret mode against
kernels.ref.paged_attention_ref
(`python -m repro.kernels.paged_attention --selftest`), and the compiled
kernel is compile-tested for a described TPU v5e (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _live_pages(length, page_size: int):
    return (length + page_size - 1) // page_size


def _paged_kernel(table_ref, len_ref, qstart_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, sm_scale: float, page_size: int,
                  window: int, q_span: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    KV, QG = q_ref.shape[1], q_ref.shape[2]
    G = QG // q_span

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[0] = jnp.zeros_like(o_ref[0])  # length-0 rows stay 0

    length = len_ref[b]
    n_live = _live_pages(length, page_size)

    @pl.when(p < n_live)
    def _accumulate():
        k_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (QG, page_size), 1)
        # row r is query position r // G at absolute position q_start + r//G
        q_abs = qstart_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (QG, page_size), 0) // G
        ok = (k_pos <= q_abs) & (k_pos < length)  # causal + live tail
        if window:  # sliding window from each query's own position
            ok &= (q_abs - k_pos) < window
        for h in range(KV):  # static: one page DMA serves every kv head
            q = q_ref[0, h].astype(jnp.float32)        # (Q*G, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (page_size, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
            probs = jnp.exp(s - m_cur[:, None])
            alpha = jnp.exp(m_prev - m_cur)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot(probs, v)
            m_ref[h] = m_cur
            l_ref[h] = l_prev * alpha + jnp.sum(probs, axis=1)

    @pl.when((p == n_live - 1) & (length > 0))
    def _done():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, :, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "q_span"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, lengths: jax.Array, *,
                    window: int = 0, interpret: bool = True,
                    q_span: int = 1,
                    q_start: jax.Array | None = None) -> jax.Array:
    """q: (B, KV, q_span*G, hd) — `q_span` query positions per sequence, the
    G heads of each position packed contiguously (position-major rows);
    k_pages/v_pages: (N, page_size, KV, hd);
    block_table: (B, P) int32 physical page ids (-1 = absent);
    lengths: (B,) int32 live tokens INCLUDING the span's writes;
    q_start: (B,) int32 absolute position of each span's first query
    (default lengths - q_span, the contiguous tail);
    window: sliding-window size (0 = full causal context).

    Returns (B, KV, q_span*G, hd).  Rows with length 0 return zeros.
    """
    B, KV, QG, hd = q.shape
    N, page_size, KVp, hdp = k_pages.shape
    assert (KV, hd) == (KVp, hdp) and v_pages.shape == k_pages.shape
    assert QG % q_span == 0, (QG, q_span)
    P = block_table.shape[1]
    sm_scale = 1.0 / math.sqrt(hd)
    lengths = lengths.astype(jnp.int32)
    if q_start is None:
        q_start = lengths - q_span

    def kv_map(b, p, table, lens, qstart):
        n_live = _live_pages(lens[b], page_size)
        pc = jnp.minimum(p, jnp.maximum(n_live - 1, 0))
        return (jnp.maximum(table[b, pc], 0), 0, 0, 0)

    def q_map(b, p, table, lens, qstart):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, KV, QG, hd), q_map),
            pl.BlockSpec((1, page_size, KV, hd), kv_map),
            pl.BlockSpec((1, page_size, KV, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, QG, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, QG, hd), jnp.float32),
            pltpu.VMEM((KV, QG), jnp.float32),
            pltpu.VMEM((KV, QG), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, sm_scale=sm_scale,
                               page_size=page_size, window=window,
                               q_span=q_span)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, QG, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths,
      q_start.astype(jnp.int32), q, k_pages, v_pages)


def _selftest() -> None:
    """Interpret-mode parity vs the pure-jnp oracle (CPU CI gate)."""
    import numpy as np

    from . import ref

    rng = np.random.default_rng(0)
    for (B, KV, G, hd, ps, P, win, Q) in [(3, 2, 4, 32, 8, 4, 0, 1),
                                          (2, 1, 8, 64, 16, 3, 0, 1),
                                          (4, 2, 2, 32, 8, 8, 16, 1),
                                          (3, 2, 4, 32, 8, 4, 0, 3),
                                          (2, 2, 2, 32, 8, 6, 16, 4)]:
        N = B * P + 1
        q = jnp.asarray(rng.standard_normal((B, KV, Q * G, hd)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((N, ps, KV, hd)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((N, ps, KV, hd)), jnp.float32)
        lengths = rng.integers(Q, P * ps + 1, size=B)
        if Q == 1:
            lengths[rng.integers(B)] = 0  # keep an inactive row in the mix
        perm = rng.permutation(np.arange(1, N))  # pages deliberately shuffled
        table = np.full((B, P), -1, np.int32)
        used = 0
        for b in range(B):
            n = -(-int(lengths[b]) // ps)
            table[b, :n] = perm[used: used + n]
            used += n
        out = paged_attention(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(lengths, jnp.int32), window=win,
                              q_span=Q, interpret=True)
        want = ref.paged_attention_ref(q, kp, vp, jnp.asarray(table),
                                       jnp.asarray(lengths, jnp.int32),
                                       window=win, q_span=Q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print(f"paged_attention parity OK: B={B} KV={KV} G={G} hd={hd} "
              f"ps={ps} P={P} window={win} q_span={Q} "
              f"lengths={lengths.tolist()}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true",
                    help="interpret-mode ref-vs-kernel parity check")
    args = ap.parse_args()
    if args.selftest:
        _selftest()
