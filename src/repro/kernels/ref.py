"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        group_size: int = 1) -> jax.Array:
    """q: (BHq, S, hd); k, v: (BHkv, S, hd); q head h uses kv head h//group."""
    BH, S, hd = q.shape
    if group_size > 1:
        k = jnp.repeat(k, group_size, axis=0)
        v = jnp.repeat(v, group_size, axis=0)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(S)
    d = pos[:, None] - pos[None, :]
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= d >= 0
    if window:
        ok &= d < window
    s = jnp.where(ok[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        layer, block_table: jax.Array, lengths: jax.Array, *,
                        window: int = 0, q_span: int = 1,
                        q_start: jax.Array | None = None) -> jax.Array:
    """Paged decode attention, gather-then-softmax oracle.

    q: (B, KV, q_span*G, hd) — `q_span` query positions per sequence in the
    grouped head layout (row j*G+g is query position j's head (kv, g));
    k_pages/v_pages: (L, N, ps, KV*hd) physical page pools of every layer,
    head-major lanes, of which layer `layer` is read; block_table:
    (B, P) int32 physical page ids (-1 = absent, masked); lengths: (B,)
    int32 live tokens per sequence (including the span's own tokens);
    q_start: (B,) absolute position of each span's first query (default
    lengths - q_span, the contiguous tail); window: sliding-window size
    (0 = full).  Rows with length 0 return zeros.
    """
    B, KV, QG, hd = q.shape
    G = QG // q_span
    _, _, ps, _ = k_pages.shape
    P = block_table.shape[1]
    if q_start is None:
        q_start = lengths - q_span
    tbl = jnp.maximum(block_table, 0)
    k = jnp.take(k_pages[layer], tbl, axis=0).reshape(B, P * ps, KV, hd)
    v = jnp.take(v_pages[layer], tbl, axis=0).reshape(B, P * ps, KV, hd)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(P * ps)[None]  # (1, S)
    q_abs = q_start[:, None] + jnp.arange(QG)[None] // G  # (B, Q*G)
    ok = ((pos < lengths[:, None])[:, None, :]  # live tail
          & (pos[:, None, :] <= q_abs[..., None]))  # per-row causal
    if window:
        ok &= (q_abs[..., None] - pos[:, None, :]) < window
    s = jnp.where(ok[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype)


def scd_pass_ref(x, y, alpha, w, mask, lam_n, sigma):
    """Sequential SCD oracle matching kernels/scd.py (per worker)."""
    K, M, F = x.shape

    def worker(xk, yk, ak, mk, sg):
        def body(i, carry):
            v, da = carry
            xi = xk[i]
            q = jnp.dot(xi, v)
            grad = 1.0 - yk[i] * q
            denom = jnp.maximum(jnp.dot(xi, xi) * sg / lam_n, 1e-12)
            a_new = jnp.clip(ak[i] + grad / denom, 0.0, 1.0)
            d = (a_new - ak[i]) * mk[i]
            v = v + (sg / lam_n) * d * yk[i] * xi
            da = da.at[i].set(d)
            return v, da

        return jax.lax.fori_loop(0, M, body, (w, jnp.zeros(M, jnp.float32)))

    v_end, da = jax.vmap(worker)(x, y, alpha, mask, sigma)
    return v_end, da


def weighted_merge_ref(updates: jax.Array, weights: jax.Array) -> jax.Array:
    return jnp.einsum("k,kn->n", weights.astype(jnp.float32),
                      updates.astype(jnp.float32)).astype(updates.dtype)
