"""Attention: GQA projections, query-blocked exact attention (XLA path),
decode-against-cache, sliding-window masks.

Layout + sharding strategy (TPU, 16-way tensor axis):
- Q heads are FLAT and PADDED to a multiple of TENSOR_PAD=16 so the head dim
  always shards over the model axis (Megatron-style head padding; smollm
  15->16, arctic 56->64, qwen1.5 20->32, whisper 12->16).  Padded heads are
  hard-masked after attention, so gradients never flow into them and the
  architecture's function is EXACTLY the unpadded one.
- K/V weights keep the compact KV head count, replicated across the model
  axis (they are small); k/v are expanded to the padded Q-head count with a
  sharded gather right before the score einsum, so scores/context stay fully
  head-parallel (no cross-shard attention math).
- KV caches store compact KV heads with the SEQUENCE dim sharded over the
  model axis (flash-decode style): decode reads are local per seq shard and
  the softmax reductions become small all-reduces; this is what makes the
  32k/500k decode caches fit.

The query-blocked formulation keeps peak score memory at
(B, H_loc, q_block, S) instead of (B, H_loc, S, S); exact softmax per row.
The Pallas flash kernel (kernels/flash_attention.py) is the TPU drop-in for
the inner block; the XLA path below is what the dry-run lowers on CPU.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import AxisRules, use_weight
from .layers import ParamDef, rms_norm, rope

NEG_INF = -1e30
TENSOR_PAD = 16  # fixed pad target == production model-axis size


def head_layout(cfg: ModelConfig) -> Tuple[str, int, int]:
    """-> (kind, H_pad, g_pad).

    'grouped': H_pad = KV * g_pad with (KV*g_pad) % 16 == 0 — q reshapes to
    (.., KV, g_pad, hd) so attention contracts against the COMPACT KV cache
    with no head-expansion gather (kv-cache traffic 1x instead of G x).
    Chosen when it costs no more padded heads than the flat layout
    (arctic/grok/jamba/vision/qwen3/h2o).
    'flat': H_pad = ceil16(H); k/v expanded by gather (smollm/whisper/
    qwen1.5, where grouped padding would blow up the head count).
    """
    h, kv = cfg.num_heads, cfg.kv_heads()
    flat_hp = ((h + TENSOR_PAD - 1) // TENSOR_PAD) * TENSOR_PAD
    g = max(h // kv, 1)
    g_pad = g
    while (kv * g_pad) % TENSOR_PAD:
        g_pad += 1
    if kv * g_pad <= flat_hp:
        return "grouped", kv * g_pad, g_pad
    return "flat", flat_hp, 0


def padded_heads(cfg: ModelConfig) -> int:
    return head_layout(cfg)[1]


def attn_defs(cfg: ModelConfig, d_model: Optional[int] = None) -> Dict[str, ParamDef]:
    d = d_model or cfg.d_model
    kv, hd = cfg.kv_heads(), cfg.head_dim_()
    hp = padded_heads(cfg)
    defs = {
        "wq": ParamDef((d, hp, hd), ("fsdp", "tensor", None)),
        "wk": ParamDef((d, kv, hd), ("fsdp", None, None)),
        "wv": ParamDef((d, kv, hd), ("fsdp", None, None)),
        "wo": ParamDef((hp, hd, d), ("tensor", None, "fsdp")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((hp, hd), ("tensor", None), init="zeros")
        defs["bk"] = ParamDef((kv, hd), (None, None), init="zeros")
        defs["bv"] = ParamDef((kv, hd), (None, None), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), init="ones")
        defs["k_norm"] = ParamDef((hd,), (None,), init="ones")
    return defs


def head_maps(cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(kv index per padded q head, padded-head validity mask)."""
    kv, h = cfg.kv_heads(), cfg.num_heads
    kind, hp, g_pad = head_layout(cfg)
    g = max(h // kv, 1)
    if kind == "grouped":
        # each kv group owns g_pad slots; the first g are real heads
        idx = jnp.arange(hp) // g_pad
        mask = (jnp.arange(hp) % g_pad) < g
    else:
        idx = jnp.minimum(jnp.arange(hp) // g, kv - 1)
        mask = jnp.arange(hp) < h
    return idx, mask


def expand_kv(cfg: ModelConfig, k: jax.Array) -> jax.Array:
    """(…, KV, hd) -> (…, H_pad, hd) via group-index gather (shardable)."""
    idx, _ = head_maps(cfg)
    return jnp.take(k, idx, axis=-2)


def qkv_project(cfg: ModelConfig, p, x: jax.Array,
                positions: Optional[jax.Array], *, rope_q: bool = True,
                rope_k: bool = True,
                rules: Optional[AxisRules] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> q (B,S,H_pad,hd), k/v (B,S,KV,hd) (compact)."""
    q = jnp.einsum("bsd,dhe->bshe", x,
                   use_weight(p["wq"], rules, "fsdp", "tensor", None))
    k = jnp.einsum("bsd,dke->bske", x,
                   use_weight(p["wk"], rules, "fsdp", None, None))
    v = jnp.einsum("bsd,dke->bske", x,
                   use_weight(p["wv"], rules, "fsdp", None, None))
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if positions is not None:
        if rope_q:
            q = rope(q, positions, cfg.rope_theta, cfg.rope_yarn)
        if rope_k:
            k = rope(k, positions, cfg.rope_theta, cfg.rope_yarn)
    return q, k, v


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window: int) -> jax.Array:
    """(Sq, Sk) additive bias from positions."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(d.shape, bool)
    if causal:
        ok &= d >= 0
    if window:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def blocked_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                      v: jax.Array, *, causal: bool = True, window: int = 0,
                      q_block: int = 512,
                      rules: Optional[AxisRules] = None) -> jax.Array:
    """Exact attention, scanned over query blocks.

    q: (B, S, H_pad, hd); k, v: (B, Sk, KV, hd) compact.
    Returns (B, S, H_pad, hd) with padded heads zeroed.
    """
    B, S, HP, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    _, hmask = head_maps(cfg)

    # full-seq attention always computes in the EXPANDED flat-head form:
    # padded heads shard cleanly over the model axis (16-way TP); the
    # expansion gather is cheap relative to S^2 score work.  (The grouped
    # compact form is used only at decode, where kv-cache read traffic
    # dominates — see decode_attention.)
    kf = expand_kv(cfg, k)  # (B, Sk, H_pad, hd)
    vf = expand_kv(cfg, v)
    if rules is not None:
        kf = jax.lax.with_sharding_constraint(
            kf, rules.sharding("batch", None, "tensor", None))
        vf = jax.lax.with_sharding_constraint(
            vf, rules.sharding("batch", None, "tensor", None))

    k_pos = jnp.arange(Sk)
    q_block = min(q_block, S)
    n_blocks = max(S // q_block, 1)
    rem = S - n_blocks * q_block

    # remat: never keep the (B, H, q_block, S) probs for backward — they are
    # the S^2 memory monster; recompute per q-block instead (flash-style).
    @jax.checkpoint
    def one_block(q_blk: jax.Array, q0: jax.Array) -> jax.Array:
        qb = q_blk.shape[1]
        bias = _mask_bias(q0 + jnp.arange(qb), k_pos, causal=causal,
                          window=window)
        # operands stay bf16 (no hoisted f32 stack converts); the MXU-style
        # f32 accumulation comes from preferred_element_type.
        qs = q_blk * q_blk.dtype.type(scale)
        scores = jnp.einsum("bqhe,bshe->bhqs", qs, kf,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(scores + bias, axis=-1)
        return jnp.einsum("bhqs,bshe->bqhe", probs.astype(vf.dtype), vf)

    if n_blocks <= 1 and rem == 0:
        out = one_block(q, jnp.int32(0))
    else:
        q_main = q[:, : n_blocks * q_block].reshape(B, n_blocks, q_block, HP, hd)

        def body(_, xs):
            q_blk, idx = xs
            return None, one_block(q_blk, idx * q_block)

        _, out = jax.lax.scan(body, None,
                              (jnp.moveaxis(q_main, 1, 0),
                               jnp.arange(n_blocks) ))
        out = jnp.moveaxis(out, 0, 1).reshape(B, n_blocks * q_block, HP, hd)
        if rem:
            tail = one_block(q[:, n_blocks * q_block:],
                             jnp.int32(n_blocks * q_block))
            out = jnp.concatenate([out, tail], axis=1)
    return out * hmask[None, None, :, None].astype(out.dtype)


def decode_attention(cfg: ModelConfig, q: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, q_pos: jax.Array, k_pos: jax.Array,
                     *, window: int = 0) -> jax.Array:
    """One-token attention against a (possibly seq-sharded) cache.

    q: (B, Q, H_pad, hd); k_cache/v_cache: (B, Sc, KV, hd);
    q_pos: scalar, (B, 1), or (B, Q) absolute query positions
    (continuous-batching slots / chunked prefill);
    k_pos: (Sc,) or (B, Sc) absolute positions (-1 = empty slot).
    """
    B, Q, HP, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    kind, _, g_pad = head_layout(cfg)
    _, hmask = head_maps(cfg)
    KV = cfg.kv_heads()
    if k_pos.ndim == 1:
        k_pos = k_pos[None, :]
    q_posv = jnp.asarray(q_pos)
    if q_posv.ndim == 0:
        q_posv = q_posv[None, None]
    d = q_posv[..., :, None] - k_pos[:, None, :]  # (B|1, Q|1, Sc)
    ok = (d >= 0) & (k_pos[:, None, :] >= 0)
    if window:
        ok &= d < window
    qs = q * q.dtype.type(scale)
    if kind == "grouped":
        # contract against the COMPACT cache — no head-expansion gather,
        # kv-cache read traffic is 1x instead of (H/KV)x.
        qg = qs.reshape(B, Q, KV, g_pad, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache,
                            preferred_element_type=jnp.float32)
        bias = jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :]
        probs = jax.nn.softmax(scores + bias, axis=-1)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v_cache.dtype),
                         v_cache).reshape(B, Q, HP, hd)
    else:
        kf = expand_kv(cfg, k_cache)
        vf = expand_kv(cfg, v_cache)
        scores = jnp.einsum("bqhe,bshe->bhqs", qs, kf,
                            preferred_element_type=jnp.float32)
        bias = jnp.where(ok, 0.0, NEG_INF)[:, None, :, :]
        probs = jax.nn.softmax(scores + bias, axis=-1)
        out = jnp.einsum("bhqs,bshe->bqhe", probs.astype(vf.dtype), vf)
    return out * hmask[None, None, :, None].astype(out.dtype)


# ---------------------------------------------------------------------------
# Paged KV: gather-through-block-table helpers (XLA path; the Pallas
# kernel in kernels/paged_attention.py skips the gather entirely)
# ---------------------------------------------------------------------------


def gather_pages(pages: jax.Array, layer, block_table: jax.Array,
                 kv_shape) -> jax.Array:
    """Layer `layer` of the (L, N, ps, KV*hd) pools + (B, P) table ->
    (B, P*ps, *kv_shape) in logical token order, gathered straight from
    the stack (no layer slice is taken).  Absent entries (-1) clamp to the
    null page; callers mask them by position validity (paged_k_pos)."""
    B, P = block_table.shape
    ps = pages.shape[2]
    seq = pages[layer, jnp.maximum(block_table, 0)]
    return seq.reshape(B, P * ps, *kv_shape)


def paged_k_pos(lengths: jax.Array, seq_len: int) -> jax.Array:
    """(B,) live lengths -> (B, seq_len) k_pos vector (-1 beyond live),
    matching the flat per-slot cache's k_pos semantics bit-for-bit."""
    pos = jnp.arange(seq_len, dtype=jnp.int32)[None]
    return jnp.where(pos < lengths[:, None], pos, -1)


def attn_out(p, ctx: jax.Array, rules: Optional[AxisRules] = None) -> jax.Array:
    return jnp.einsum("bshe,hed->bsd", ctx,
                      use_weight(p["wo"], rules, "tensor", None, "fsdp"))
