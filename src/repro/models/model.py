"""Top-level model API: init / specs / loss / prefill / decode for every family.

All entry points are pure functions of (cfg, params, ...) so they jit/pjit
cleanly and can be lowered with ShapeDtypeStructs for the multi-pod dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..sharding import AxisRules
from . import transformer as tfm
from .layers import ParamDef, cross_entropy, init_tree, rms_norm, sds_tree, spec_tree

AUX_LOSS_COEF = 0.01


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    nb = tfm.n_blocks(cfg)
    stack = lambda defs: jax.tree.map(  # noqa: E731
        lambda d: d.stacked(nb), defs, is_leaf=lambda x: isinstance(x, ParamDef))
    defs: Dict[str, Any] = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("tensor", "fsdp"),
                          init="small"),
        "final_ln": ParamDef((cfg.d_model,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                ("tensor", "fsdp"), init="small")
    if cfg.family == "audio":
        defs["blocks"] = jax.tree.map(
            lambda d: d.stacked(cfg.num_layers),
            tfm.block_defs(cfg, "xdec"),
            is_leaf=lambda x: isinstance(x, ParamDef))
        enc = jax.tree.map(lambda d: d.stacked(cfg.encoder_layers),
                           tfm.block_defs(cfg, "dense"),
                           is_leaf=lambda x: isinstance(x, ParamDef))
        defs["encoder"] = {"blocks": enc,
                           "final_ln": ParamDef((cfg.d_model,), (None,), init="ones")}
    else:
        defs["blocks"] = stack(tfm.block_defs(cfg))
    return defs


def init_params(cfg: ModelConfig, key: jax.Array) -> Any:
    return init_tree(param_defs(cfg), key, jnp.dtype(cfg.dtype))


def param_specs(cfg: ModelConfig, rules: AxisRules) -> Any:
    return spec_tree(param_defs(cfg), rules)


def param_sds(cfg: ModelConfig) -> Any:
    return sds_tree(param_defs(cfg), jnp.dtype(cfg.dtype))


def count_params(cfg: ModelConfig) -> int:
    leaves = jax.tree.leaves(param_defs(cfg),
                             is_leaf=lambda x: isinstance(x, ParamDef))
    return int(sum(np.prod(d.shape) for d in leaves))


def _block_type(cfg: ModelConfig) -> str:
    return "xdec" if cfg.family == "audio" else cfg.family


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _logits(cfg, params, x):
    x = rms_norm(x, params["final_ln"])
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return jnp.einsum("bsd,vd->bsv", x, head)


def _scan_layers(cfg: ModelConfig, window: Optional[int], make_body, x,
                 blocks, *, state=None, pools=None):
    """Run the stacked layers over x: one scan of a period of layers a step
    (`tfm.layer_kinds`; a model of one kind is a period of one), the
    period's layers unrolled, each traced with its own window and RoPE.
    `make_body(layer_cfg, window)` gives `body(x, (layer params, s)) ->
    (x, (new s, extras))`; `blocks` and the extras are stacked over layers.

    state: per-layer state stacked over layers, or None.  `s` is the
    layer's own slice; the state is scanned in and out like the blocks.
    pools: state that every layer reads and writes whole (the paged KV
    pools).  `s` is `(pools, layer index)`; the pools ride in the carry and
    are never sliced per layer.  Returns (x, new state or new pools,
    extras).

    An MoE layer's expert weights stay whole, with the layer's index beside
    them: the grouped kernel reads the layer's experts in place (a slice
    per step would copy them all)."""
    kinds = tfm.layer_kinds(cfg, window)
    P = len(kinds)
    bodies = [make_body(*k) for k in kinds]
    experts = None
    if cfg.family == "moe":
        moe = dict(blocks["moe"])
        experts = {k: moe.pop(k) for k in ("gate", "up", "down")}
        blocks = dict(blocks, moe=moe)

    # stacks over layers are grouped (L/P, P, ...), a period a scan step,
    # and layer i of a period is [i] of it.  A period of one keeps (L, ...):
    # on a TPU an (L, 1, D) view of an (L, D) stack is a relayout, not a
    # bitcast.
    def group(t):
        return t if P == 1 else jax.tree.map(
            lambda a: a.reshape((-1, P) + a.shape[1:]), t)

    def ungroup(t):
        return t if P == 1 else jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), t)

    def layer_of(t, i):
        return t if P == 1 else jax.tree.map(lambda a: a[i], t)

    def period(carry, xs):
        x, pl, j = carry
        bp, st = xs
        outs = []
        for i, body in enumerate(bodies):
            layer = j * P + i
            lp = layer_of(bp, i)
            if experts is not None:
                lp = dict(lp, moe=dict(lp["moe"], layer=layer, **experts))
            if pools is None:
                x, out = body(x, (lp, layer_of(st, i)))
            else:
                x, (pl, ex) = body(x, (lp, (pl, layer)))
                out = (None, ex)
            outs.append(out)
        outs = outs[0] if P == 1 else jax.tree.map(
            lambda *o: jnp.stack(o), *outs)
        return (x, pl, j + 1), outs

    (x, pools, _), (state, extras) = jax.lax.scan(
        period, (x, pools, jnp.int32(0)), group((blocks, state)))
    state, extras = ungroup((state, extras))
    return x, (state if pools is None else pools), extras


def _sum_stats(stats):
    """Per-layer MoE counters (L, N) -> their sum over layers (None for a
    model without MoE layers)."""
    return None if stats is None else jnp.sum(stats, axis=0)


def _encoder_forward(cfg, enc_params, frames, rules, remat):
    """Whisper encoder over stub frame embeddings (B, T, D), bidirectional."""
    x = frames

    def body(x, bp):
        x, _, _, _ = tfm.block_apply(cfg, bp, x, None, block_type="dense",
                                     causal=False, rules=rules)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, enc_params["blocks"])
    return rms_norm(x, enc_params["final_ln"])


def forward(cfg: ModelConfig, params, tokens: jax.Array, *,
            memory: Optional[jax.Array] = None, rules: AxisRules,
            window: Optional[int] = None, remat: bool = True,
            return_cache: bool = False, q_block: int = 512):
    """Full-sequence forward.  tokens: (B, S).

    memory: stub embeddings for vlm (patches) / audio (frames).
    Returns (logits, aux_loss) or (logits, aux_loss, cache) if return_cache.
    """
    logits, aux, cache, _ = _forward(
        cfg, params, tokens, memory=memory, rules=rules, window=window,
        remat=remat, return_cache=return_cache, q_block=q_block)
    if return_cache:
        return logits, aux, cache
    return logits, aux


def _forward(cfg: ModelConfig, params, tokens: jax.Array, *,
             memory: Optional[jax.Array], rules: AxisRules,
             window: Optional[int], remat: bool, return_cache: bool,
             q_block: int, valid: Optional[jax.Array] = None):
    """`forward`, returning (logits, aux, cache or None, MoE counters
    summed over layers or None).  valid: optional (B, S) bool of real
    tokens (MoE layers compute no rows for the rest)."""
    B, S = tokens.shape
    bt = _block_type(cfg)
    x = _embed(cfg, params, tokens)
    if rules is not None:
        x = jax.lax.with_sharding_constraint(
            x, rules.sharding("batch", "seq", None))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    if cfg.family == "audio":
        memory = _encoder_forward(cfg, params["encoder"], memory, rules, remat)

    from ..sharding import constrain_fwd_only

    def make_body(lcfg, win):
        def body(x, xs):
            bp, cache = xs
            x, new_cache, aux, stats = tfm.block_apply(
                lcfg, bp, x, positions, block_type=bt, window=win,
                cache=cache, memory=memory, rules=rules, q_block=q_block,
                valid=valid)
            # primal-only: shrinks the saved residual stack (seq-parallel)
            # without pinning the cotangent layout (see
            # sharding.constrain_fwd_only)
            if rules is not None:
                x = constrain_fwd_only(x, rules.sharding("batch", "seq", None))
            return x, (new_cache, (aux, stats))

        return jax.checkpoint(body) if remat else body

    caches = None
    if return_cache:
        shapes = tfm.block_cache_shapes(
            cfg, B, S, bt, cross_len=memory.shape[1] if memory is not None else 0)
        dummy = {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}
        nb = tfm.n_blocks(cfg)
        caches = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (nb,) + a.shape), dummy)
    x, cache, (auxs, stats) = _scan_layers(cfg, window, make_body, x,
                                           params["blocks"], state=caches)

    logits = _logits(cfg, params, x)
    aux = jnp.sum(auxs) if auxs is not None else jnp.float32(0.0)
    return logits, aux, cache, _sum_stats(stats)


def loss_fn(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            rules: AxisRules, remat: bool = True, q_block: int = 512,
            total_weight: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chicle-weighted LM loss.

    batch: tokens (B,S) int32, labels (B,S) int32, weights (B,) float32 —
    the per-example weights carry the uni-task chunk weighting |D_k|/|D̂|
    (Stich 2018): grad(loss) == the weighted merge of per-worker updates.

    total_weight: pass the FULL global-batch weight sum when this call sees
    only a microbatch (gradient accumulation) so microbatch grads sum to the
    exact full-batch gradient.
    """
    logits, aux = forward(cfg, params, batch["tokens"],
                          memory=batch.get("memory"), rules=rules, remat=remat,
                          q_block=q_block)
    ce = cross_entropy(logits, batch["labels"])  # (B, S)
    w = batch["weights"].astype(jnp.float32)
    per_ex = jnp.mean(ce, axis=-1)
    total_w = (jnp.maximum(jnp.sum(w), 1e-9) if total_weight is None
               else total_weight)
    loss = jnp.sum(per_ex * w) / total_w
    metrics = {"loss": loss, "aux_loss": aux}
    return loss + AUX_LOSS_COEF * aux, metrics


# ---------------------------------------------------------------------------
# Caches / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, B: int, cache_len: int, *,
               cross_len: int = 0, per_slot: bool = False) -> Dict[str, Any]:
    """per_slot: per-ROW k_pos (B, cache_len) so every sequence tracks its
    own fill depth (continuous-batching slot pools); default is one shared
    (cache_len,) vector for lockstep batches."""
    bt = _block_type(cfg)
    nb = tfm.n_blocks(cfg)
    shapes = tfm.block_cache_shapes(cfg, B, cache_len, bt, cross_len=cross_len)
    blocks = {k: jnp.zeros((nb,) + s, d) for k, (s, d) in shapes.items()}
    cache: Dict[str, Any] = {"blocks": blocks}
    if bt != "ssm":
        shape = (B, cache_len) if per_slot else (cache_len,)
        cache["k_pos"] = jnp.full(shape, -1, jnp.int32)
    return cache


def cache_sds(cfg: ModelConfig, B: int, cache_len: int, *,
              cross_len: int = 0) -> Dict[str, Any]:
    bt = _block_type(cfg)
    nb = tfm.n_blocks(cfg)
    shapes = tfm.block_cache_shapes(cfg, B, cache_len, bt, cross_len=cross_len)
    blocks = {k: jax.ShapeDtypeStruct((nb,) + s, d) for k, (s, d) in shapes.items()}
    cache: Dict[str, Any] = {"blocks": blocks}
    if bt != "ssm":
        cache["k_pos"] = jax.ShapeDtypeStruct((cache_len,), jnp.int32)
    return cache


def cache_specs(cfg: ModelConfig, rules: AxisRules) -> Dict[str, Any]:
    bt = _block_type(cfg)
    specs: Dict[str, Any] = {"blocks": tfm.cache_specs_for(cfg, rules, bt)}
    if bt != "ssm":
        from jax.sharding import PartitionSpec as P
        specs["k_pos"] = P(None)
    return specs


def decode_step(cfg: ModelConfig, params, cache, token: jax.Array,
                pos: jax.Array, *, rules: AxisRules,
                window: Optional[int] = None,
                ring: bool = False) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step.  token: (B, 1) int32; pos: scalar int32 OR a (B,)
    vector of per-row positions (continuous-batching slot pools, paired with
    a per-row (B, cache_len) k_pos from ``init_cache(per_slot=True)``).

    Returns (logits (B, 1, V), new cache).
    """
    bt = _block_type(cfg)
    x = _embed(cfg, params, token)
    if rules is not None:
        x = jax.lax.with_sharding_constraint(
            x, rules.sharding("batch", None, None))

    if bt != "ssm":
        k_pos = cache["k_pos"]
        W = k_pos.shape[-1]
        if k_pos.ndim == 2:  # per-row cache: each row tracks its own depth
            B = k_pos.shape[0]
            posv = (pos if jnp.ndim(pos)
                    else jnp.full((B,), pos, jnp.int32))  # lockstep batch
            idx = posv % W if ring else jnp.minimum(posv, W - 1)
            k_pos = k_pos.at[jnp.arange(B), idx].set(posv)
        else:
            if jnp.ndim(pos):
                raise ValueError(
                    "vector pos requires a per-row k_pos — build the cache "
                    "with init_cache(per_slot=True) or prefill(true_len=...)")
            idx = pos % W if ring else jnp.minimum(pos, W - 1)
            k_pos = k_pos.at[idx].set(pos)
    else:
        k_pos = None

    def make_body(lcfg, win):
        def body(x, xs):
            bp, bc = xs
            x, new_bc = tfm.block_decode(lcfg, bp, x, pos, k_pos, bc,
                                         block_type=bt, window=win,
                                         ring=ring, rules=rules)
            return x, (new_bc, None)
        return body

    x, new_blocks, _ = _scan_layers(cfg, window, make_body, x,
                                    params["blocks"], state=cache["blocks"])
    logits = _logits(cfg, params, x)
    new_cache = dict(cache, blocks=new_blocks)
    if k_pos is not None:
        new_cache["k_pos"] = k_pos
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged KV cache (block tables over fixed-size token pages)
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: ModelConfig, n_pages: int,
                     page_size: int) -> Dict[str, Any]:
    """Physical K/V page pools: {"blocks": {"k","v": (nb, n_pages, ps,
    KV*hd)}}, a token row's kv heads side by side (head-major lanes), the
    layout the paged kernel reads and a TPU lays out unpadded.  Page 0 is
    the engine's reserved null page (masked writes land there).  Per-slot
    fill depth lives in the block table + lengths the caller threads
    through `paged_step`; there is no device-side k_pos state.
    dense/moe only (flat recurrent state does not page)."""
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged cache supports dense/moe; got {cfg.family!r}")
    nb = tfm.n_blocks(cfg)
    dt = jnp.dtype(cfg.dtype)
    shape = (nb, n_pages, page_size, cfg.kv_heads() * cfg.head_dim_())
    return {"blocks": {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}}


def _cow_pages(blocks, cow):
    """Copy-on-write share breaks: copy page `src` onto page `dst` in every
    layer of both pools before the step's rows land in `dst` (the block
    table already names it); rows without a break pass the null page for
    both, which copies onto itself."""
    if cow is None:
        return blocks
    src, dst = cow
    return {k: a.at[:, dst].set(a[:, src]) for k, a in blocks.items()}


def paged_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
               pos: jax.Array, table: jax.Array, lengths: jax.Array, *,
               rules: AxisRules, window: Optional[int] = None,
               impl: str = "xla",
               cow: Optional[Tuple[jax.Array, jax.Array]] = None,
               stats: bool = False):
    """One paged step over a span of Q new tokens a row, in one dispatch:
    a decode step (Q = 1), a speculative verify (Q = k + 1: the current
    token and k drafts) or one chunk of a chunked prefill (Q = the chunk).

    tokens: (B, Q) int32, right-padded; pos: (B,) absolute position of each
    row's first token; table: (B, P) block table; lengths: (B,) live tokens,
    the span's real ones included (0 = inactive row: its output is garbage
    and its writes go to the null page).  Each token attends the row's
    paged context plus the span's earlier tokens (causal by absolute
    position), and its K/V rows are written into their pages in place
    (`transformer.block_decode_paged`); padding past a row's length is
    causally invisible to its real tokens.

    cow: optional ((B,), (B,)) int32 (src, dst) page pairs — copy-on-write
    share breaks fused into the step (`_cow_pages`; only a span's first
    page can be shared, so one pair a row suffices).

    Returns (logits (B, Q, V), new cache), and with `stats` (MoE models)
    the (moe.N_STATS,) int32 expert counters summed over layers."""
    Q = tokens.shape[1]
    x = _embed(cfg, params, tokens)
    if rules is not None:
        x = jax.lax.with_sharding_constraint(
            x, rules.sharding("batch", None, None))
    q_pos = pos.astype(jnp.int32)[:, None] + jnp.arange(Q, dtype=jnp.int32)

    def make_body(lcfg, win):
        def body(x, xs):
            bp, (pools, layer) = xs
            x, new_pools, moe = tfm.block_decode_paged(
                lcfg, bp, x, q_pos, table, lengths, pools, layer,
                window=win, rules=rules, impl=impl)
            return x, (new_pools, moe)
        return body

    x, new_blocks, moe = _scan_layers(
        cfg, window, make_body, x, params["blocks"],
        pools=_cow_pages(cache["blocks"], cow))
    out = (_logits(cfg, params, x), dict(cache, blocks=new_blocks))
    return out + (_sum_stats(moe),) if stats else out


# ---------------------------------------------------------------------------
# Speculative verification (one batched forward over k+1 draft positions)
# ---------------------------------------------------------------------------


def verify_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
                pos: jax.Array, n_new: jax.Array, *, rules: AxisRules,
                window: Optional[int] = None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Flat-layout speculative verification: score a SPAN of Q candidate
    tokens per row in ONE forward instead of Q sequential decodes.

    tokens: (B, Q) int32 — [current token, draft_1..draft_{Q-1}] per row,
    right-padded; pos: (B,) absolute position of each row's first token
    (its pending KV write position); n_new: (B,) real tokens in the span
    (1 + accepted-draft budget; 0 = inactive row, nothing written).

    Requires the per-row (B, cache_len) k_pos cache from
    ``init_cache(per_slot=True)`` / ``prefill(true_len=...)``.  Position j's
    logits equal a sequential `decode_step` at that position bit-for-bit
    (drafts beyond a mismatch are causally invisible to earlier positions,
    so rollback is just "ignore the tail").  Returns (logits (B, Q, V),
    new cache)."""
    bt = _block_type(cfg)
    if bt not in ("dense", "moe"):
        raise NotImplementedError(f"verify supports dense/moe; got {bt!r}")
    B, Q = tokens.shape
    q_pos = pos.astype(jnp.int32)[:, None] + jnp.arange(Q, dtype=jnp.int32)
    valid = jnp.arange(Q, dtype=jnp.int32)[None] < n_new[:, None]
    x = _embed(cfg, params, tokens)
    if rules is not None:
        x = jax.lax.with_sharding_constraint(
            x, rules.sharding("batch", None, None))

    k_pos = cache["k_pos"]
    if k_pos.ndim != 2:
        raise ValueError("verify_step needs a per-row k_pos — build the "
                         "cache with init_cache(per_slot=True) or "
                         "prefill(true_len=...)")
    W = k_pos.shape[-1]
    rows = jnp.arange(B)[:, None]
    # invalid OR out-of-range positions index past W and are dropped —
    # never clamped onto the last live row
    idx = jnp.where(valid, q_pos, W)
    k_pos = k_pos.at[rows, idx].set(q_pos, mode="drop")

    def make_body(lcfg, win):
        def body(x, xs):
            bp, bc = xs
            x, new_bc = tfm.block_verify(lcfg, bp, x, q_pos, valid, k_pos,
                                         bc, window=win, rules=rules)
            return x, (new_bc, None)
        return body

    x, new_blocks, _ = _scan_layers(cfg, window, make_body, x,
                                    params["blocks"], state=cache["blocks"])
    logits = _logits(cfg, params, x)
    return logits, dict(cache, blocks=new_blocks, k_pos=k_pos)


def prefill(cfg: ModelConfig, params, tokens: jax.Array, *,
            memory: Optional[jax.Array] = None, rules: AxisRules,
            window: Optional[int] = None, remat: bool = True,
            q_block: int = 512, cache_len: Optional[int] = None,
            true_len: Optional[jax.Array] = None, stats: bool = False):
    """Prefill: forward over the prompt, returning last-token logits + a
    decode cache.  cache_len > S allocates headroom for subsequent decode
    steps (k/v seq dims zero-padded, empty slots marked -1 in k_pos).

    true_len: optional (B,) real prompt lengths when rows are right-padded to
    a shared bucket length (serving).  Returned logits are taken at each
    row's true last token and k_pos becomes per-row (B, cache_len) with pad
    positions masked out (-1), matching ``init_cache(per_slot=True)``.

    stats: also return the (moe.N_STATS,) int32 expert counters summed
    over layers (MoE models), pad tokens past true_len left out."""
    B, S = tokens.shape
    valid = (None if true_len is None or not cfg.num_experts else
             jnp.arange(S, dtype=jnp.int32)[None] < true_len[:, None])
    logits, aux, blocks, moe = _forward(
        cfg, params, tokens, memory=memory, rules=rules, window=window,
        remat=remat, return_cache=True, q_block=q_block, valid=valid)
    out = _prefill_out(cfg, logits, blocks, tokens, cache_len, true_len)
    return out + (moe,) if stats else out


def _prefill_out(cfg, logits, blocks, tokens, cache_len, true_len):
    B, S = tokens.shape
    bt = _block_type(cfg)
    cache_len = cache_len or S
    if cache_len > S and bt != "ssm":
        pad = cache_len - S
        seq_axis = 3 if bt == "vlm" else 2  # stacked (nb, [k-1,] B, S, kv, hd)
        def pad_kv(name, arr):
            if name in ("k", "v"):
                widths = [(0, 0)] * arr.ndim
                widths[seq_axis] = (0, pad)
                return jnp.pad(arr, widths)
            return arr
        blocks = {k: pad_kv(k, v) for k, v in blocks.items()}
    cache: Dict[str, Any] = {"blocks": blocks}
    if true_len is not None:
        if bt != "ssm":
            pos_row = jnp.arange(cache_len, dtype=jnp.int32)[None]
            cache["k_pos"] = jnp.where(pos_row < true_len[:, None],
                                       pos_row, -1)
        last = jnp.take_along_axis(
            logits, (true_len - 1).astype(jnp.int32)[:, None, None], axis=1)
        return last, cache
    if bt != "ssm":
        cache["k_pos"] = jnp.concatenate([
            jnp.arange(S, dtype=jnp.int32),
            jnp.full((max(cache_len - S, 0),), -1, jnp.int32)])
    return logits[:, -1:], cache
