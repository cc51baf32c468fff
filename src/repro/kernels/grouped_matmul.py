"""Grouped matrix product over the experts a chip holds (Pallas TPU).

`expert_matmul(rows, weights, group_sizes, layer)`: `rows` (m, k) are
sorted by group; `group_sizes` (G + 1,) counts the rows of each of the G
held groups, then, last, the rows that belong to none (routed to experts
held elsewhere, or padding), which sit at the end.  `weights` (L, G, k, n)
are every layer's, and the kernel reads layer `layer`'s in place: a
slice taken outside would be copied whole, every expert of it, before the
call.  Row r of a held group g gives `rows[r] @ weights[layer, g]`; every
other row gives 0.  Only held rows
are computed: a 2,048-token prefill does not run every held expert on
every row, and a decode step reads each touched expert's weights once.

The grid is (n tiles, row-tile visits, k tiles), as in the installed
megablox `gmm`, whose tile plan (`make_group_metadata`) this reuses: a
row tile that two groups share is visited once by each, its store masked
to the group's rows, and the tiles past the held rows are never visited.
The rows past the held ones are zeroed after the call.
The row tile is 16 rows for decode-sized inputs and 128 from 2,048 rows
on (`row_tile`); k and n tiles are the largest multiple of 128 dividing
the width, at most 1,024.  The output's name in a device trace is the
custom call `expert_matmul`.

Gradients (training) come from `jax.lax.ragged_dot`, the XLA form of the
same product, through a custom VJP.  Correctness is checked on the CPU in
interpret mode against a plain einsum (`tests/test_grouped_matmul.py`),
and the compiled kernel for a described TPU v5e
(`tests/test_tpu_compile.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

_MOST = 1024  # widest k or n tile


def row_tile(m: int) -> int:
    """Rows a tile: small for decode, where few rows reach each expert."""
    return 128 if m >= 2048 else 16


def _tile(dim: int) -> int:
    t = min(_MOST, dim) // 128 * 128
    while t >= 128:
        if dim % t == 0:
            return t
        t -= 128
    return dim


def _plan(group_sizes, m: int, tm: int):
    """megablox's tile plan for the held groups alone (the last group of
    `group_sizes` is never visited): ((offsets, group ids, row-tile ids),
    number of visits)."""
    return make_group_metadata(group_sizes=group_sizes, m=m, tm=tm,
                               start_group=jnp.int32(0),
                               num_nonzero_groups=group_sizes.shape[0] - 1,
                               visit_empty_groups=False)


def rows_computed(group_sizes, m: int):
    """Rows the kernel computes for `m` rows under `group_sizes`, tile
    padding included (row-tile visits x tile rows)."""
    tm = row_tile(m)
    return _plan(group_sizes, -(-m // tm) * tm, tm)[1] * tm


def _kernel(meta, layer, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm: int,
            tiles_k: int):
    offsets, group_ids, m_tiles = meta
    v, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == tiles_k - 1)
    def _store():
        g = group_ids[v]
        row = m_tiles[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype),
                                 out_ref[...])


def _forward(rows, weights, group_sizes, layer, interpret: bool):
    m, k = rows.shape
    _, G, k2, n = weights.shape
    assert k == k2 and group_sizes.shape == (G + 1,), (
        rows.shape, weights.shape, group_sizes.shape)
    tm = row_tile(m)
    pad = -m % tm
    if pad:  # padding rows join the last (never visited) group
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[G].add(pad)
    tk, tn = _tile(k), _tile(n)
    meta, visits = _plan(group_sizes.astype(jnp.int32), m + pad, tm)

    def lhs_map(j, v, kk, meta, layer):
        return meta[2][v], kk

    def rhs_map(j, v, kk, meta, layer):
        return layer[0], meta[1][v], kk, j

    def out_map(j, v, kk, meta, layer):
        return meta[2][v], j

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=k // tk),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, visits, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, jnp.reshape(layer, (1,)).astype(jnp.int32), rows, weights)
    # rows past the held ones are never stored (their tiles are not
    # visited, or the visit's store is masked to its group)
    held = jnp.arange(m)[:, None] < meta[0][G]
    return jnp.where(held, out[:m], jnp.zeros((), out.dtype))


def expert_matmul_xla(rows, weights, group_sizes):
    """The same product in XLA (`jax.lax.ragged_dot`); the last group's
    rows multiply a zero matrix."""
    zero = jnp.zeros((1,) + weights.shape[1:], weights.dtype)
    return jax.lax.ragged_dot(rows, jnp.concatenate([weights, zero]),
                              group_sizes.astype(jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _expert_matmul(rows, weights, group_sizes, layer, interpret):
    return _forward(rows, weights, group_sizes, layer, interpret)


def _fwd(rows, weights, group_sizes, layer, interpret):
    return (_forward(rows, weights, group_sizes, layer, interpret),
            (rows, weights, group_sizes, layer))


def _bwd(interpret, res, g):
    rows, weights, group_sizes, layer = res
    w = jax.lax.dynamic_index_in_dim(weights, layer, 0, keepdims=False)
    _, vjp = jax.vjp(lambda r, w: expert_matmul_xla(r, w, group_sizes),
                     rows, w)
    d_rows, d_w = vjp(g)
    d_weights = jax.lax.dynamic_update_index_in_dim(
        jnp.zeros_like(weights), d_w, layer, 0)
    return d_rows, d_weights, None, None


_expert_matmul.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_matmul(rows: jax.Array, weights: jax.Array,
                  group_sizes: jax.Array, layer=0, *,
                  interpret: bool = True) -> jax.Array:
    """rows (m, k) sorted by group, weights (L, G, k, n), group_sizes
    (G + 1,) int32 summing to m, layer: int32 scalar in [0, L) -> (m, n)
    in rows' dtype, float32 accumulation."""
    return _expert_matmul(rows, weights, group_sizes,
                          jnp.asarray(layer, jnp.int32), interpret)
