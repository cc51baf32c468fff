"""Paged-attention decode Pallas TPU kernel (block-table gather, O(live)).

One QUERY SPAN per sequence (Q=1 plain decode; Q=k+1 speculative
verification, where the span is [current token, k draft tokens]; Q=chunk
for a chunked-prefill chunk) attends a KV cache scattered across
fixed-size physical pages of one pool, which stays in HBM.

The pool.  Every layer's pages sit in one stack `(L, N, page_size,
KV*hd)`: a page is `page_size` token rows of all KV heads side by side,
`KV*hd` lanes (head-major), which a TPU lays out unpadded and row-major
for both head widths in use (8 x 80 = 5 x 128 lanes, 4 x 128).  The
kernel reads layer `layer`'s pages in place, the layer index
scalar-prefetched, so the model's layer scan hands it the whole pool and
never slices, relayouts or restacks it.

Blocks of pages.  The grid is (B, R / RB, ceil(P / ppb)): step (b, r, j)
covers query rows r*RB .. r*RB + RB - 1 of sequence b against its logical
pages j*ppb .. j*ppb + ppb - 1.  The pool is passed as ppb K operands and
ppb V operands (the same arrays; XLA adds no copy), each with a one-page
BlockSpec `(None, None, page_size, KV*hd)` at `(layer, page, 0, 0)`, and
the Pallas pipeline double-buffers every operand: the next block's page
DMAs run while the current block computes.  Which page operand i fetches
at block j is computed once per call, outside the kernel, by
`_fetch_plan` and SCALAR-PREFETCHED (`pltpu.PrefetchScalarGridSpec`) with
the layer, the lengths and the query starts, so an index map is one SMEM
load; scalar work per page DMA is what bounded the page-at-a-time kernel.
Past a sequence's live length an operand repeats the page it held at the
previous step and the pipeline starts no DMA: a block wholly past the
live length fetches nothing and skips its compute, and the dead tail of a
partial last block costs no DMA and is masked.  The kernel never reads a
page the sequence does not own; per-sequence work is O(live tokens), not
O(table width).

Compute per block, every kv head in one contraction.  Outside the kernel
each query row is spread into its own kv head's `hd` lanes of a `KV*hd`
row, zeros elsewhere; row h*Q*G + i is kv head h's row i (query position
i // G).  A page block is `(ppb*page_size, KV*hd)` lane-dense token rows,
so one `(RB, T) = q . k^T` MXU contraction over the lanes scores each row
against its own head's keys only (the zeros take the other heads out) in
the pool's dtype with float32 accumulation, and the softmax runs over the
T tokens, masked by the causal / sliding-window / live-tail positions.
The online softmax (acc/m/l in VMEM scratch across blocks) and P.V stay
in float32: for a bfloat16 pool, P is split exactly into three bfloat16
terms (P = p1 + p2 + p3, 24 significand bits) that share one pass of V
through the MXU, so every product is exact and the sum accumulates in
float32.  P.V gives `(RB, KV*hd)`; after the call each row keeps its own
head's lanes.  A Q > 1 span masks each row at its own absolute position,
so verifying k drafts costs ONE sweep instead of k+1.

Block sizes.  `_block_sizes` picks (RB, ppb) from the shapes alone: every
query row (all kv heads: `H*hd` lanes are 128-aligned only at H = KV when
hd = 80) and `_BLOCK_TOKENS` tokens a block at decode widths; fewer tokens
as the score tile grows with the rows (verify spans, chunked prefill), and
fewer rows a step only where their accumulator is too large (long
chunks); at least one page and at most the table width.
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
_BLOCK_TOKENS = 512           # tokens a block at decode widths
_SCORE_TILE = 1 << 17         # float32 elements of one block's score tile
_ACC_TILE = 1 << 18           # float32 elements of a step's accumulator


def _live_pages(length, page_size: int):
    return (length + page_size - 1) // page_size


def _block_sizes(page_size: int, rows: int, lanes: int,
                 n_pages: int) -> tuple[int, int]:
    """(query rows, pages) a grid step covers, of a sequence's `rows` rows
    of `lanes` lanes.  Every row, unless their (rows, lanes) accumulator
    would pass `_ACC_TILE` elements (a long chunked-prefill span): then
    halve the rows while the half stays a multiple of 16.  Pages:
    `_BLOCK_TOKENS` tokens, fewer where the (rows, tokens) score tile would
    pass `_SCORE_TILE`; at least 1 and at most the table width `n_pages`."""
    rb = rows
    while rb % 32 == 0 and rb * lanes > _ACC_TILE:
        rb //= 2
    tokens = min(_BLOCK_TOKENS, _SCORE_TILE // rb)
    return rb, max(1, min(tokens // page_size, n_pages))


def _fetch_plan(block_table, lengths, page_size: int, ppb: int):
    """(B, ceil(P / ppb) * ppb) physical page that K/V operand i fetches at
    block j, in column j*ppb + i: the table's page while it is live; past
    the live length the operand's own last live page again (a repeated
    block index, so the pipeline starts no DMA); an operand with no live
    page takes the sequence's last page, or the null page 0."""
    B, P = block_table.shape
    n_live = _live_pages(lengths, page_size)[:, None]
    col = jnp.arange(pl.cdiv(P, ppb) * ppb, dtype=jnp.int32)[None]
    i = col % ppb
    last = i + (n_live - 1 - i) // ppb * ppb
    logical = jnp.where(n_live > i, jnp.minimum(col, last),
                        jnp.maximum(n_live - 1, 0))
    page = jnp.take_along_axis(block_table.astype(jnp.int32), logical, axis=1)
    return jnp.maximum(page, 0)


def _exact_parts(p, dtype):
    """float32 `p` as terms of `dtype` whose float32 sum is `p`: p itself
    for a float32 pool; three bfloat16 terms (8 significand bits each)
    for a narrower one."""
    if dtype == jnp.float32:
        return [p]
    parts = []
    for _ in range(3):
        part = p.astype(dtype)
        parts.append(part)
        p = p - part.astype(jnp.float32)
    return parts


def _paged_kernel(layer_ref, pages_ref, len_ref, qstart_ref, q_ref, *refs,
                  sm_scale: float, page_size: int, ppb: int, window: int,
                  head_rows: int, group: int):
    del layer_ref, pages_ref  # read by the index maps only
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * ppb:]
    b = pl.program_id(0)
    r = pl.program_id(1)          # block of query rows
    j = pl.program_id(2)          # block of pages
    RB = q_ref.shape[0]
    T = ppb * page_size           # tokens a block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)  # length-0 rows stay 0

    length = len_ref[b]
    n_blocks = (length + T - 1) // T

    @pl.when(j < n_blocks)
    def _accumulate():
        dt = k_refs[0].dtype
        k = jnp.concatenate([p[...] for p in k_refs], axis=0)  # (T, KV*hd)
        v = jnp.concatenate([p[...] for p in v_refs], axis=0)
        q = q_ref[...].astype(dt)                              # (RB, KV*hd)
        row = r * RB + jax.lax.broadcasted_iota(jnp.int32, (RB, 1), 0)
        k_pos = j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        q_abs = qstart_ref[b] + (row % head_rows) // group
        ok = (k_pos <= q_abs) & (k_pos < length)   # causal + live tail
        if window:  # sliding window from each query's own position
            ok &= (q_abs - k_pos) < window
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        probs = jnp.where(ok, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        parts = _exact_parts(probs, dt)
        pv = jax.lax.dot(jnp.concatenate(parts, axis=0), v,
                         preferred_element_type=jnp.float32)
        pv = sum(pv[i * RB:(i + 1) * RB] for i in range(len(parts)))
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_cur
        l_ref[...] = l_prev * alpha + jnp.sum(probs, axis=1, keepdims=True)

    @pl.when((j == n_blocks - 1) & (length > 0))
    def _done():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "q_span"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    layer: jax.Array, block_table: jax.Array,
                    lengths: jax.Array, *, window: int = 0,
                    interpret: bool = True, q_span: int = 1,
                    q_start: jax.Array | None = None) -> jax.Array:
    """q: (B, KV, q_span*G, hd) — `q_span` query positions per sequence, the
    G heads of each position packed contiguously (position-major rows);
    k_pages/v_pages: (L, N, page_size, KV*hd) every layer's pages, of which
    layer `layer` (int32 scalar) is read;
    block_table: (B, P) int32 physical page ids (-1 = absent);
    lengths: (B,) int32 live tokens INCLUDING the span's writes;
    q_start: (B,) int32 absolute position of each span's first query
    (default lengths - q_span, the contiguous tail);
    window: sliding-window size (0 = full causal context).

    Returns (B, KV, q_span*G, hd).  Rows with length 0 return zeros.
    """
    B, KV, QG, hd = q.shape
    _, _, page_size, lanes = k_pages.shape
    assert lanes == KV * hd and v_pages.shape == k_pages.shape, (
        q.shape, k_pages.shape, v_pages.shape)
    assert QG % q_span == 0, (QG, q_span)
    P = block_table.shape[1]
    R = KV * QG
    RB, ppb = _block_sizes(page_size, R, lanes, P)
    sm_scale = 1.0 / math.sqrt(hd)
    lengths = lengths.astype(jnp.int32)
    if q_start is None:
        q_start = lengths - q_span

    # row h*QG + i holds q[:, h, i] in lanes h*hd .. h*hd + hd - 1
    own = jnp.eye(KV, dtype=q.dtype)[None, :, None, :, None]
    q_rows = (q[:, :, :, None, :] * own).reshape(B, R, lanes)
    pages = _fetch_plan(block_table, lengths, page_size, ppb)

    def kv_map(i):
        return lambda b, r, j, layer, pages, lens, qstart: (
            layer[0], pages[b, j * ppb + i], 0, 0)

    def q_map(b, r, j, layer, pages, lens, qstart):
        return (b, r, 0)

    kv_specs = [pl.BlockSpec((None, None, page_size, lanes), kv_map(i))
                for i in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, R // RB, pl.cdiv(P, ppb)),
        in_specs=[pl.BlockSpec((None, RB, lanes), q_map)] + kv_specs * 2,
        out_specs=pl.BlockSpec((None, RB, lanes), q_map),
        scratch_shapes=[
            pltpu.VMEM((RB, lanes), jnp.float32),
            pltpu.VMEM((RB, 1), jnp.float32),
            pltpu.VMEM((RB, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, sm_scale=sm_scale,
                               page_size=page_size, ppb=ppb, window=window,
                               head_rows=QG, group=QG // q_span)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, lanes), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pages, lengths,
      q_start.astype(jnp.int32), q_rows, *[k_pages] * ppb, *[v_pages] * ppb)
    # each row keeps its own head's lanes: (B, KV, QG, KV, hd) -> diagonal
    out = jnp.diagonal(out.reshape(B, KV, QG, KV, hd), axis1=1, axis2=3)
    return jnp.moveaxis(out, -1, 1)


# (B, KV, G, hd, page_size, P, window, q_span[, lengths]): the first five
# fit one block; the next five span three or more (`_block_sizes` gives 8
# pages of 64 tokens), with lengths ending mid-block and on a block
# boundary, a table width that is no multiple of the block, a sliding
# window shorter than a block and Q > 1 spans that cross a block boundary;
# then a chunk (one page a step), danube's (8 x 80 lanes) and mellum's
# (4 x 128) heads, and a chunk long enough to split its rows over steps
SELFTEST_CASES = [
    (3, 2, 4, 32, 8, 4, 0, 1),
    (2, 1, 8, 64, 16, 3, 0, 1),
    (4, 2, 2, 32, 8, 8, 16, 1),
    (3, 2, 4, 32, 8, 4, 0, 3),
    (2, 2, 2, 32, 8, 6, 16, 4),
    (3, 2, 4, 32, 64, 26, 0, 1, (0, 1100, 1024)),
    (3, 2, 4, 32, 64, 26, 100, 1, (0, 1600, 700)),
    (3, 2, 2, 32, 64, 30, 0, 4, (0, 1026, 1537)),
    (2, 2, 2, 32, 64, 27, 150, 5, (514, 1700)),
    (2, 2, 4, 32, 64, 8, 0, 130, (0, 400)),
    (3, 8, 4, 80, 16, 40, 0, 1, (0, 333, 640)),
    (2, 4, 8, 128, 16, 40, 200, 1, (517, 640)),
    (2, 2, 4, 64, 64, 12, 0, 520, (600, 0)),
]


def random_case(case, rng, dtype=jnp.float32, layers=1):
    """Inputs for one case: q, pools of `layers` layers, a block table over
    shuffled physical pages and the lengths (random in [q_span, P*page_size]
    unless given)."""
    import numpy as np

    B, KV, G, hd, ps, P, _, Q, *fixed = case
    N = B * P + 1
    q = jnp.asarray(rng.standard_normal((B, KV, Q * G, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((layers, N, ps, KV * hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((layers, N, ps, KV * hd)), dtype)
    lengths = (np.asarray(fixed[0]) if fixed
               else rng.integers(Q, P * ps + 1, size=B))
    perm = rng.permutation(np.arange(1, N))  # pages deliberately shuffled
    table = np.full((B, P), -1, np.int32)
    used = 0
    for b in range(B):
        n = -(-int(lengths[b]) // ps)
        table[b, :n] = perm[used: used + n]
        used += n
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


def _selftest() -> None:
    """Interpret-mode parity vs the pure-jnp oracle (CPU CI gate), reading
    layer 1 of a 3-layer pool."""
    import numpy as np

    from . import ref

    rng = np.random.default_rng(0)
    for case in SELFTEST_CASES:
        B, KV, G, hd, ps, P, win, Q, *fixed = case
        q, kp, vp, table, lengths = random_case(case, rng, layers=3)
        if Q == 1 and not fixed:
            lengths = lengths.at[rng.integers(B)].set(0)  # an inactive row
        out = paged_attention(q, kp, vp, 1, table, lengths, window=win,
                              q_span=Q, interpret=True)
        want = ref.paged_attention_ref(q, kp, vp, 1, table, lengths,
                                       window=win, q_span=Q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        rb, ppb = _block_sizes(ps, KV * Q * G, KV * hd, P)
        print(f"paged_attention parity OK: B={B} KV={KV} G={G} hd={hd} "
              f"ps={ps} P={P} blocks={-(-P // ppb)}x{ppb} "
              f"rows={KV * Q * G // rb}x{rb} window={win} q_span={Q} "
              f"lengths={np.asarray(lengths).tolist()}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true",
                    help="interpret-mode ref-vs-kernel parity check")
    args = ap.parse_args()
    if args.selftest:
        _selftest()
