"""The grouped expert kernel (`kernels/grouped_matmul.py`) in interpret
mode against a plain einsum: each held group's rows times its expert's
matrix, zero for the rows of the last (not held) group, with row tiles of
16 and of 128, groups that share a tile, empty groups, a held range that
is empty, the layer read from a stack, and gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul import (expert_matmul, expert_matmul_xla,
                                          row_tile, rows_computed)


def _einsum(rows, weights, sizes, layer):
    """out[r] = rows[r] @ weights[layer, group of r]; 0 in the last group."""
    G = weights.shape[1]
    w = np.concatenate([np.asarray(weights[layer]),
                        np.zeros((1,) + weights.shape[2:], np.float32)])
    gid = np.repeat(np.arange(G + 1), sizes)
    return np.einsum("mk,mkn->mn", np.asarray(rows), w[gid])


# (rows, k, n, held groups, layers, group sizes; None = random)
CASES = [
    (112, 256, 128, 4, 1, None),           # decode-sized: 16-row tiles
    (2048, 128, 256, 3, 2, None),          # 128-row tiles, layer 1 of 2
    (40, 64, 32, 2, 1, [13, 0, 27]),       # padded to 48 rows, an empty group
    (64, 128, 128, 3, 1, [0, 0, 0, 64]),   # no row held: all zero
    (96, 2304 // 9, 896 // 7, 4, 1, [5, 17, 1, 40, 33]),
]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_matches_einsum(case):
    m, k, n, G, L, sizes = CASES[case]
    rng = np.random.default_rng(case)
    if sizes is None:
        sizes = rng.multinomial(m, np.ones(G + 1) / (G + 1))
        sizes[-1] += sizes[1]
        sizes[1] = 0  # an empty held group between full ones
    sizes = np.asarray(sizes, np.int32)
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((L, G, k, n)), jnp.float32)
    layer = L - 1
    out = expert_matmul(rows, w, jnp.asarray(sizes), layer, interpret=True)
    want = _einsum(rows, w, sizes, layer)
    # float32 sums of k products in another order
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(expert_matmul_xla(rows, w[layer], jnp.asarray(sizes))),
        want, rtol=1e-5, atol=1e-4)
    # the computed rows: tile visits x tile rows, never fewer than held
    tm = row_tile(m)
    got = int(rows_computed(jnp.asarray(sizes), m))
    assert got % tm == 0 and got >= sizes[:-1].sum()
    starts = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    visits = sum((s + c - 1) // tm - s // tm + 1
                 for s, c in zip(starts[:-1], sizes[:-1]) if c)
    assert got == visits * tm


def test_gradients_match_einsum():
    rng = np.random.default_rng(9)
    sizes = jnp.asarray([9, 0, 20, 11], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 3, 32, 16)), jnp.float32)

    def loss(r, w):
        return jnp.sum(jnp.sin(expert_matmul(r, w, sizes, 1, interpret=True)))

    def loss_ref(r, w):
        gid = jnp.repeat(jnp.arange(4), sizes, total_repeat_length=40)
        wz = jnp.concatenate([w[1], jnp.zeros((1, 32, 16))])
        return jnp.sum(jnp.sin(jnp.einsum("mk,mkn->mn", r, wz[gid])))

    got = jax.grad(loss, (0, 1))(rows, w)
    want = jax.grad(loss_ref, (0, 1))(rows, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(got[1][0]).max()) == 0.0  # the other layer
