"""Block-composed transformer backbones for all assigned families.

A model is embedding -> scan over homogeneous BLOCKS -> final norm -> tied
logits.  A block bundles the family's repeating pattern so lax.scan sees one
body (small HLO, FSDP all-gather per block):

  dense:   1 x (self-attn + swiglu)
  moe:     1 x (self-attn + moe-ffn [+ dense residual])
  hybrid:  `attn_every` sub-layers: 1 attn + (attn_every-1) mamba, ffn
           alternating dense/moe per `moe_every`
  vlm:     (cross_attn_every-1) x (self+mlp) + 1 x (cross-attn+mlp)
  xdec:    1 x (self-attn + cross-attn + mlp)     (whisper decoder)
  ssm:     1 x (rwkv6 time-mix + channel-mix)

Each block type provides defs / train / prefill / decode and its cache slice.
Caches are pytrees stacked over blocks; scan maps over (params, cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import AxisRules
from . import attention as attn
from . import moe as moe_mod
from . import ssm
from .layers import ParamDef, mlp_defs, rms_norm, swiglu


def n_blocks(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_every
    if cfg.family == "vlm":
        return cfg.num_layers // cfg.cross_attn_every
    return cfg.num_layers


def layers_per_block(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.family == "vlm":
        return cfg.cross_attn_every
    return 1


# ---------------------------------------------------------------------------
# Param defs per block
# ---------------------------------------------------------------------------


def _ln(d):
    return ParamDef((d,), (None,), init="ones")


def block_defs(cfg: ModelConfig, block_type: Optional[str] = None) -> Dict[str, Any]:
    d = cfg.d_model
    bt = block_type or cfg.family
    if bt == "dense":
        return {"ln1": _ln(d), "attn": attn.attn_defs(cfg),
                "ln2": _ln(d), "mlp": mlp_defs(d, cfg.d_ff)}
    if bt == "moe":
        return {"ln1": _ln(d), "attn": attn.attn_defs(cfg),
                "ln2": _ln(d), "moe": moe_mod.moe_defs(cfg)}
    if bt == "hybrid":
        k = cfg.attn_every
        n_moe = sum(1 for i in range(k) if cfg.num_experts and i % cfg.moe_every == 1)
        n_dense = k - n_moe
        defs: Dict[str, Any] = {
            "ln_mix": _ln(d).stacked(k),
            "ln_ffn": _ln(d).stacked(k),
            "attn": attn.attn_defs(cfg),
            "mamba": jax.tree.map(lambda p: p.stacked(k - 1), ssm.mamba_defs(cfg),
                                  is_leaf=lambda x: isinstance(x, ParamDef)),
            "mlp": jax.tree.map(lambda p: p.stacked(n_dense), mlp_defs(d, cfg.d_ff),
                                is_leaf=lambda x: isinstance(x, ParamDef)),
        }
        if n_moe:
            defs["moe"] = jax.tree.map(lambda p: p.stacked(n_moe),
                                       moe_mod.moe_defs(cfg),
                                       is_leaf=lambda x: isinstance(x, ParamDef))
        return defs
    if bt == "vlm":
        k = cfg.cross_attn_every
        return {
            "ln1": _ln(d).stacked(k), "ln2": _ln(d).stacked(k),
            "self": jax.tree.map(lambda p: p.stacked(k - 1), attn.attn_defs(cfg),
                                 is_leaf=lambda x: isinstance(x, ParamDef)),
            "cross": attn.attn_defs(cfg),
            "cross_gate": ParamDef((1,), (None,), init="zeros"),
            "mlp": jax.tree.map(lambda p: p.stacked(k), mlp_defs(d, cfg.d_ff),
                                is_leaf=lambda x: isinstance(x, ParamDef)),
        }
    if bt == "xdec":  # whisper decoder layer
        return {"ln1": _ln(d), "self": attn.attn_defs(cfg),
                "ln_x": _ln(d), "cross": attn.attn_defs(cfg),
                "ln2": _ln(d), "mlp": mlp_defs(d, cfg.d_ff)}
    if bt == "ssm":
        return {"ln1": _ln(d), "att": ssm.rwkv_defs(cfg),
                "ln2": _ln(d), "ffn": ssm.rwkv_ffn_defs(cfg)}
    raise ValueError(f"unknown block type {bt}")


# ---------------------------------------------------------------------------
# Cache slices per block (shapes only; allocation in model.py)
# ---------------------------------------------------------------------------


def block_cache_shapes(cfg: ModelConfig, B: int, cache_len: int,
                       block_type: Optional[str] = None,
                       cross_len: int = 0) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """name -> (shape, dtype) for ONE block (without the leading block dim)."""
    bt = block_type or cfg.family
    kv, hd, d = cfg.kv_heads(), cfg.head_dim_(), cfg.d_model
    di = cfg.ssm_expand * d
    w = cfg.ssm_conv_width
    n = cfg.ssm_state_dim
    dt = jnp.dtype(cfg.dtype)
    out: Dict[str, Tuple[Tuple[int, ...], Any]] = {}
    if bt in ("dense", "moe", "xdec"):
        out["k"] = ((B, cache_len, kv, hd), dt)
        out["v"] = ((B, cache_len, kv, hd), dt)
    if bt == "hybrid":
        out["k"] = ((B, cache_len, kv, hd), dt)
        out["v"] = ((B, cache_len, kv, hd), dt)
        out["conv"] = ((cfg.attn_every - 1, B, w - 1, di), dt)
        out["h"] = ((cfg.attn_every - 1, B, di, n), jnp.float32)
    if bt == "vlm":
        k = cfg.cross_attn_every
        out["k"] = ((k - 1, B, cache_len, kv, hd), dt)
        out["v"] = ((k - 1, B, cache_len, kv, hd), dt)
        out["xk"] = ((B, cross_len, kv, hd), dt)
        out["xv"] = ((B, cross_len, kv, hd), dt)
    if bt == "xdec":
        out["xk"] = ((B, cross_len, kv, hd), dt)
        out["xv"] = ((B, cross_len, kv, hd), dt)
    if bt == "ssm":
        out["shift_a"] = ((B, 1, d), dt)
        out["shift_f"] = ((B, 1, d), dt)
        out["wkv"] = ((B, d // cfg.rwkv_head_dim, cfg.rwkv_head_dim,
                       cfg.rwkv_head_dim), jnp.float32)
    return out


def cache_specs_for(cfg: ModelConfig, rules: AxisRules,
                    block_type: Optional[str] = None) -> Dict[str, Any]:
    """PartitionSpecs matching block_cache_shapes (WITH leading block dim)."""
    bt = block_type or cfg.family
    P = rules.spec
    out: Dict[str, Any] = {}
    # KV caches: compact KV heads; SEQUENCE dim sharded over the model axis
    # (flash-decode style) — this is what makes 32k/500k decode caches fit.
    if bt in ("dense", "moe", "xdec", "hybrid"):
        out["k"] = P(None, "cache_batch", "tensor", None, None)
        out["v"] = P(None, "cache_batch", "tensor", None, None)
    if bt == "hybrid":
        out["conv"] = P(None, None, "cache_batch", None, "tensor")
        out["h"] = P(None, None, "cache_batch", "tensor", None)
    if bt == "vlm":
        out["k"] = P(None, None, "cache_batch", "tensor", None, None)
        out["v"] = P(None, None, "cache_batch", "tensor", None, None)
        out["xk"] = P(None, "cache_batch", "tensor", None, None)
        out["xv"] = P(None, "cache_batch", "tensor", None, None)
    if bt == "xdec":
        out["xk"] = P(None, "cache_batch", "tensor", None, None)
        out["xv"] = P(None, "cache_batch", "tensor", None, None)
    if bt == "ssm":
        out["shift_a"] = P(None, "cache_batch", None, None)
        out["shift_f"] = P(None, "cache_batch", None, None)
        out["wkv"] = P(None, "cache_batch", "tensor", None, None)
    return out


# ---------------------------------------------------------------------------
# Sub-layer helpers
# ---------------------------------------------------------------------------


def _self_attn_full(cfg, p, x, positions, *, window, causal=True, q_block=512,
                    rules=None):
    q, k, v = attn.qkv_project(cfg, p, x, positions, rules=rules)
    ctx = attn.blocked_attention(cfg, q, k, v, causal=causal, window=window,
                                 q_block=q_block, rules=rules)
    return attn.attn_out(p, ctx, rules), k, v


def _write_cache(cache_k, cache_v, k, v, pos, ring: bool):
    """Write S new entries at pos (S=1 decode; S=seq prefill from 0).

    pos may be a (B,) vector (continuous-batching slots: every sequence sits
    at its own depth) — then S must be 1 and each row scatters independently.
    """
    S = k.shape[1]
    if jnp.ndim(pos):
        B = k.shape[0]
        W = cache_k.shape[1]
        idx = pos % W if ring else jnp.minimum(pos, W - 1)
        cache_k = cache_k.at[jnp.arange(B), idx].set(k[:, 0])
        cache_v = cache_v.at[jnp.arange(B), idx].set(v[:, 0])
        return cache_k, cache_v
    if ring:
        W = cache_k.shape[1]
        idx = pos % W
        cache_k = cache_k.at[:, idx].set(k[:, 0])
        cache_v = cache_v.at[:, idx].set(v[:, 0])
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, pos, axis=1)
    return cache_k, cache_v


def _self_attn_decode(cfg, p, x, pos, k_pos, cache_k, cache_v, *, window,
                      ring, rules=None):
    """x: (B,1,D).  pos: scalar or (B,) per-slot.  Returns (out, k, v)."""
    B = x.shape[0]
    if jnp.ndim(pos):
        positions = pos.reshape(B, 1).astype(jnp.int32)
        q_pos = positions  # (B, 1) broadcasts against (B, Sc) k_pos
    else:
        positions = jnp.full((B, 1), pos, jnp.int32)
        q_pos = pos
    q, k, v = attn.qkv_project(cfg, p, x, positions, rules=rules)
    cache_k, cache_v = _write_cache(cache_k, cache_v, k, v, pos, ring)
    ctx = attn.decode_attention(cfg, q, cache_k, cache_v, q_pos, k_pos,
                                window=window)
    return attn.attn_out(p, ctx, rules), cache_k, cache_v


def _cross_attn(cfg, p, x, xk, xv, rules=None):
    q, _, _ = attn.qkv_project(cfg, p, x, None, rules=rules)
    ctx = attn.decode_attention(
        cfg, q, xk, xv, jnp.int32(2 ** 30),
        jnp.zeros((xk.shape[1],), jnp.int32))
    return attn.attn_out(p, ctx, rules)


def cross_kv(cfg, p, memory):
    """K/V projections of the cross-attended memory (enc out / patches)."""
    _, k, v = attn.qkv_project(cfg, p, memory, None)
    return k, v


# ---------------------------------------------------------------------------
# Block apply: train/prefill unified (cache=None -> train)
# ---------------------------------------------------------------------------


def block_apply(cfg: ModelConfig, bp, x, positions, *, block_type=None,
                window=0, cache=None, memory=None, rules: AxisRules = None,
                causal=True, q_block=512):
    """Full-sequence block application (train + prefill).

    Returns (x, new_cache, aux_loss).
    """
    bt = block_type or cfg.family
    aux = jnp.float32(0.0)

    if bt in ("dense", "moe"):
        h, k, v = _self_attn_full(cfg, bp["attn"], rms_norm(x, bp["ln1"]),
                                  positions, window=window, causal=causal,
                                  q_block=q_block, rules=rules)
        x = x + h
        h2 = rms_norm(x, bp["ln2"])
        if bt == "moe":
            f, aux = moe_mod.moe_ffn(cfg, bp["moe"], h2, rules)
        else:
            f = swiglu(h2, bp["mlp"]["gate"], bp["mlp"]["up"], bp["mlp"]["down"], rules)
        x = x + f
        new_cache = None if cache is None else dict(cache, k=k, v=v)
        return x, new_cache, aux

    if bt == "hybrid":
        k_sub = cfg.attn_every
        new_cache = dict(cache) if cache is not None else None
        n_moe_used = 0
        n_dense_used = 0
        convs, hs = [], []
        for i in range(k_sub):
            h_in = rms_norm(x, bp["ln_mix"][i])
            if i == 0:
                h, kk, vv = _self_attn_full(cfg, bp["attn"], h_in, positions,
                                            window=window, causal=causal,
                                            q_block=q_block, rules=rules)
                if new_cache is not None:
                    new_cache["k"], new_cache["v"] = kk, vv
            else:
                mp = jax.tree.map(lambda a: a[i - 1], bp["mamba"])
                st = None
                if cache is not None:
                    st = (cache["conv"][i - 1], cache["h"][i - 1])
                h, (cs, hn) = ssm.mamba_forward(cfg, mp, h_in, st, rules=rules)
                convs.append(cs)
                hs.append(hn)
            x = x + h
            h2 = rms_norm(x, bp["ln_ffn"][i])
            if cfg.num_experts and i % cfg.moe_every == 1:
                mo = jax.tree.map(lambda a: a[n_moe_used], bp["moe"])
                f, a = moe_mod.moe_ffn(cfg, mo, h2, rules)
                aux = aux + a
                n_moe_used += 1
            else:
                ml = jax.tree.map(lambda a: a[n_dense_used], bp["mlp"])
                f = swiglu(h2, ml["gate"], ml["up"], ml["down"], rules)
                n_dense_used += 1
            x = x + f
        if new_cache is not None:
            new_cache["conv"] = jnp.stack(convs)
            new_cache["h"] = jnp.stack(hs)
        return x, new_cache, aux

    if bt == "vlm":
        k_sub = cfg.cross_attn_every
        new_cache = dict(cache) if cache is not None else None
        ks, vs = [], []
        for i in range(k_sub):
            h_in = rms_norm(x, bp["ln1"][i])
            if i < k_sub - 1:
                sp = jax.tree.map(lambda a: a[i], bp["self"])
                h, kk, vv = _self_attn_full(cfg, sp, h_in, positions,
                                            window=window, causal=causal,
                                            q_block=q_block, rules=rules)
                ks.append(kk)
                vs.append(vv)
            else:
                if cache is not None and "xk" in cache and memory is None:
                    xk, xv = cache["xk"], cache["xv"]
                else:
                    xk, xv = cross_kv(cfg, bp["cross"], memory)
                h = jnp.tanh(bp["cross_gate"]) * _cross_attn(
                    cfg, bp["cross"], h_in, xk, xv, rules)
                if new_cache is not None:
                    new_cache["xk"], new_cache["xv"] = xk, xv
            x = x + h
            ml = jax.tree.map(lambda a: a[i], bp["mlp"])
            x = x + swiglu(rms_norm(x, bp["ln2"][i]), ml["gate"], ml["up"],
                           ml["down"], rules)
        if new_cache is not None:
            new_cache["k"] = jnp.stack(ks)
            new_cache["v"] = jnp.stack(vs)
        return x, new_cache, aux

    if bt == "xdec":
        h, k, v = _self_attn_full(cfg, bp["self"], rms_norm(x, bp["ln1"]),
                                  positions, window=window, causal=causal,
                                  q_block=q_block, rules=rules)
        x = x + h
        if cache is not None and "xk" in cache and memory is None:
            xk, xv = cache["xk"], cache["xv"]
        else:
            xk, xv = cross_kv(cfg, bp["cross"], memory)
        x = x + _cross_attn(cfg, bp["cross"], rms_norm(x, bp["ln_x"]), xk,
                            xv, rules)
        ml = bp["mlp"]
        x = x + swiglu(rms_norm(x, bp["ln2"]), ml["gate"], ml["up"], ml["down"], rules)
        new_cache = None if cache is None else dict(cache, k=k, v=v, xk=xk, xv=xv)
        return x, new_cache, aux

    if bt == "ssm":
        h, shift_a, wkv = ssm.rwkv_time_mix(
            cfg, bp["att"], rms_norm(x, bp["ln1"]),
            cache["shift_a"] if cache is not None else jnp.zeros(
                (x.shape[0], 1, x.shape[-1]), x.dtype),
            cache["wkv"] if cache is not None else jnp.zeros(
                (x.shape[0], x.shape[-1] // cfg.rwkv_head_dim,
                 cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32))
        x = x + h
        h2, shift_f = ssm.rwkv_channel_mix(
            cfg, bp["ffn"], rms_norm(x, bp["ln2"]),
            cache["shift_f"] if cache is not None else jnp.zeros(
                (x.shape[0], 1, x.shape[-1]), x.dtype))
        x = x + h2
        new_cache = None if cache is None else dict(
            cache, shift_a=shift_a, shift_f=shift_f, wkv=wkv)
        return x, new_cache, aux

    raise ValueError(f"unknown block type {bt}")


# ---------------------------------------------------------------------------
# Block apply: paged decode / chunked prefill (token positions -> pages)
# ---------------------------------------------------------------------------


def block_decode_paged(cfg: ModelConfig, bp, x, q_pos, table, lengths, cache,
                       *, window=0, rules: AxisRules = None, impl="xla",
                       cow=None):
    """Paged-KV block step over new tokens x: (B, Q, D) at positions
    q_pos: (B, Q).  Q == 1 is decode; Q > 1 is one chunked-prefill chunk.

    cache: {"k": (N, ps, KV, hd), "v": ...} physical page pools shared by
    every sequence; table: (B, P) int32 block table (-1 absent);
    lengths: (B,) live tokens INCLUDING the new ones (0 = inactive row:
    its writes route to the null page and its output is garbage).

    cow: optional (src, dst) pair of (B,) int32 page ids for copy-on-write
    share breaks: rows whose write position lands in a page shared with
    another sequence have the page payload copied src -> dst BEFORE the new
    rows scatter (the table already names dst), fused into this dispatch so
    a break costs no extra launch.  Rows with no break use src == dst == 0
    (the null page copies onto itself).

    New-token K/V rows scatter into exactly the owning pages (O(new tokens)
    writes — no pool-wide copy); attention gathers K/V through the table so
    only the P pages the table names are ever read.  dense/moe only.
    """
    bt = cfg.family
    if bt not in ("dense", "moe"):
        raise NotImplementedError(f"paged decode supports dense/moe; got {bt!r}")
    B, Q, _ = x.shape
    ps = cache["k"].shape[1]
    P = table.shape[1]

    h_in = rms_norm(x, bp["ln1"])
    q, k, v = attn.qkv_project(cfg, bp["attn"], h_in, q_pos, rules=rules)

    ck, cv = cache["k"], cache["v"]
    if cow is not None:
        # copy-on-write page break: move the shared page's payload into the
        # slot's private copy before this step's rows land in it
        cow_src, cow_dst = cow
        ck = ck.at[cow_dst].set(ck[cow_src])
        cv = cv.at[cow_dst].set(cv[cow_src])

    # scatter the Q new K/V rows into their pages; tokens past a row's live
    # length (padding / inactive rows) route to the reserved null page 0
    valid = q_pos < lengths[:, None]
    pidx = jnp.take_along_axis(table, jnp.minimum(q_pos // ps, P - 1), axis=1)
    pg = jnp.where(valid, jnp.maximum(pidx, 0), 0).reshape(-1)
    off = (q_pos % ps).reshape(-1)
    ck = ck.at[pg, off].set(k.reshape((B * Q,) + k.shape[2:]))
    cv = cv.at[pg, off].set(v.reshape((B * Q,) + v.shape[2:]))

    if impl == "pallas":
        kind, HP, g_pad = attn.head_layout(cfg)
        if kind != "grouped":
            raise NotImplementedError(
                "pallas paged decode needs the grouped head layout")
        from ..kernels.ops import interpret_mode
        from ..kernels.paged_attention import paged_attention
        KVh, hd = cfg.kv_heads(), cfg.head_dim_()
        # (B, Q, KV*g_pad, hd) -> (B, KV, Q*g_pad, hd): the kernel rides the
        # Q span along the row dim, position-major (row j*g_pad+g)
        qg = (q.reshape(B, Q, KVh, g_pad, hd)
              .transpose(0, 2, 1, 3, 4).reshape(B, KVh, Q * g_pad, hd))
        ctx = paged_attention(qg, ck, cv, table, lengths, window=window,
                              q_span=Q, q_start=q_pos[:, 0],
                              interpret=interpret_mode())
        _, hmask = attn.head_maps(cfg)
        ctx = (ctx.reshape(B, KVh, Q, g_pad, hd)
               .transpose(0, 2, 1, 3, 4).reshape(B, Q, HP, hd))
        ctx = ctx * hmask[None, None, :, None].astype(ctx.dtype)
    else:
        kseq = attn.gather_pages(ck, table)
        vseq = attn.gather_pages(cv, table)
        k_pos = attn.paged_k_pos(lengths, P * ps)
        ctx = attn.decode_attention(cfg, q, kseq, vseq, q_pos, k_pos,
                                    window=window)
    x = x + attn.attn_out(bp["attn"], ctx, rules)
    h2 = rms_norm(x, bp["ln2"])
    if bt == "moe":
        f, _ = moe_mod.moe_ffn(cfg, bp["moe"], h2, rules)
    else:
        f = swiglu(h2, bp["mlp"]["gate"], bp["mlp"]["up"], bp["mlp"]["down"],
                   rules)
    return x + f, dict(cache, k=ck, v=cv)


# ---------------------------------------------------------------------------
# Block apply: flat-cache multi-token verify (speculative decode)
# ---------------------------------------------------------------------------


def block_verify(cfg: ModelConfig, bp, x, q_pos, valid, k_pos, cache, *,
                 window=0, rules: AxisRules = None):
    """Flat-cache block step over a SPAN of new tokens x: (B, Q, D) at
    per-row positions q_pos: (B, Q) — the speculative-verify twin of
    `block_decode` (Q=1) on the (B, cache_len) per-slot cache layout.

    valid: (B, Q) bool marks real tokens; invalid positions (draft padding,
    inactive rows) write NOTHING (out-of-bounds scatter with mode="drop")
    and their outputs are garbage the caller discards.  Each valid query
    attends the row's previous context plus the span's earlier tokens
    (causal by absolute position via k_pos/q_pos), so the Q logits match Q
    sequential `block_decode` calls bit-for-bit.  dense/moe only.
    """
    bt = cfg.family
    if bt not in ("dense", "moe"):
        raise NotImplementedError(f"verify supports dense/moe; got {bt!r}")
    B, Q, _ = x.shape
    W = cache["k"].shape[1]

    h_in = rms_norm(x, bp["ln1"])
    q, k, v = attn.qkv_project(cfg, bp["attn"], h_in, q_pos, rules=rules)
    # scatter the span's K/V rows at their absolute positions; invalid
    # rows index out of bounds and are dropped (no null row in the flat
    # layout, so masked writes must not land anywhere)
    rows = jnp.arange(B)[:, None]
    # out-of-range valid positions drop too (fail-safe, never clamp onto
    # the newest live row)
    idx = jnp.where(valid, q_pos, W)
    ck = cache["k"].at[rows, idx].set(k, mode="drop")
    cv = cache["v"].at[rows, idx].set(v, mode="drop")
    ctx = attn.decode_attention(cfg, q, ck, cv, q_pos, k_pos, window=window)
    x = x + attn.attn_out(bp["attn"], ctx, rules)
    h2 = rms_norm(x, bp["ln2"])
    if bt == "moe":
        f, _ = moe_mod.moe_ffn(cfg, bp["moe"], h2, rules)
    else:
        f = swiglu(h2, bp["mlp"]["gate"], bp["mlp"]["up"], bp["mlp"]["down"],
                   rules)
    return x + f, dict(cache, k=ck, v=cv)


# ---------------------------------------------------------------------------
# Block apply: decode (single token)
# ---------------------------------------------------------------------------


def block_decode(cfg: ModelConfig, bp, x, pos, k_pos, cache, *,
                 block_type=None, window=0, ring=False, rules: AxisRules = None):
    """x: (B, 1, D).  Returns (x, new_cache)."""
    bt = block_type or cfg.family
    new_cache = dict(cache)

    if bt in ("dense", "moe"):
        h, nk, nv = _self_attn_decode(cfg, bp["attn"], rms_norm(x, bp["ln1"]),
                                      pos, k_pos, cache["k"], cache["v"],
                                      window=window, ring=ring, rules=rules)
        new_cache["k"], new_cache["v"] = nk, nv
        x = x + h
        h2 = rms_norm(x, bp["ln2"])
        if bt == "moe":
            f, _ = moe_mod.moe_ffn(cfg, bp["moe"], h2, rules)
        else:
            f = swiglu(h2, bp["mlp"]["gate"], bp["mlp"]["up"], bp["mlp"]["down"], rules)
        return x + f, new_cache

    if bt == "hybrid":
        k_sub = cfg.attn_every
        convs, hs = [], []
        n_moe_used = n_dense_used = 0
        for i in range(k_sub):
            h_in = rms_norm(x, bp["ln_mix"][i])
            if i == 0:
                h, nk, nv = _self_attn_decode(cfg, bp["attn"], h_in, pos, k_pos,
                                              cache["k"], cache["v"],
                                              window=window, ring=ring,
                                              rules=rules)
                new_cache["k"], new_cache["v"] = nk, nv
            else:
                mp = jax.tree.map(lambda a: a[i - 1], bp["mamba"])
                h, (cs, hn) = ssm.mamba_forward(
                    cfg, mp, h_in, (cache["conv"][i - 1], cache["h"][i - 1]),
                    rules=rules)
                convs.append(cs)
                hs.append(hn)
            x = x + h
            h2 = rms_norm(x, bp["ln_ffn"][i])
            if cfg.num_experts and i % cfg.moe_every == 1:
                mo = jax.tree.map(lambda a: a[n_moe_used], bp["moe"])
                f, _ = moe_mod.moe_ffn(cfg, mo, h2, rules)
                n_moe_used += 1
            else:
                ml = jax.tree.map(lambda a: a[n_dense_used], bp["mlp"])
                f = swiglu(h2, ml["gate"], ml["up"], ml["down"], rules)
                n_dense_used += 1
            x = x + f
        new_cache["conv"] = jnp.stack(convs)
        new_cache["h"] = jnp.stack(hs)
        return x, new_cache

    if bt == "vlm":
        k_sub = cfg.cross_attn_every
        nks, nvs = [], []
        for i in range(k_sub):
            h_in = rms_norm(x, bp["ln1"][i])
            if i < k_sub - 1:
                sp = jax.tree.map(lambda a: a[i], bp["self"])
                h, nk, nv = _self_attn_decode(cfg, sp, h_in, pos, k_pos,
                                              cache["k"][i], cache["v"][i],
                                              window=window, ring=ring,
                                              rules=rules)
                nks.append(nk)
                nvs.append(nv)
            else:
                h = jnp.tanh(bp["cross_gate"]) * _cross_attn(
                    cfg, bp["cross"], h_in, cache["xk"], cache["xv"], rules)
            x = x + h
            ml = jax.tree.map(lambda a: a[i], bp["mlp"])
            x = x + swiglu(rms_norm(x, bp["ln2"][i]), ml["gate"], ml["up"],
                           ml["down"], rules)
        new_cache["k"] = jnp.stack(nks)
        new_cache["v"] = jnp.stack(nvs)
        return x, new_cache

    if bt == "xdec":
        h, nk, nv = _self_attn_decode(cfg, bp["self"], rms_norm(x, bp["ln1"]),
                                      pos, k_pos, cache["k"], cache["v"],
                                      window=window, ring=ring, rules=rules)
        new_cache["k"], new_cache["v"] = nk, nv
        x = x + h
        x = x + _cross_attn(cfg, bp["cross"], rms_norm(x, bp["ln_x"]),
                            cache["xk"], cache["xv"], rules)
        ml = bp["mlp"]
        x = x + swiglu(rms_norm(x, bp["ln2"]), ml["gate"], ml["up"], ml["down"], rules)
        return x, new_cache

    if bt == "ssm":
        x, new_cache, _ = block_apply(cfg, bp, x,
                                      jnp.full((x.shape[0], 1), pos, jnp.int32),
                                      block_type="ssm", cache=cache, rules=rules)
        return x, new_cache

    raise ValueError(f"unknown block type {bt}")
