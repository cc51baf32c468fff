"""jit-able train / prefill / serve steps + ShapeDtypeStruct input builders.

These are the functions the dry-run lowers for every (arch x shape x mesh)
combination and that launch/train.py runs for real on host devices.

The Chicle uni-task weighting is first-class here: train batches carry a
per-example ``weights`` vector assembled by data.ChunkBatchPipeline from the
chunk->worker table; the weighted-mean loss makes the gradient equal the
paper's |D_k|/|D̂|-weighted merge without touching the compiled step when
workers scale in/out or chunks move.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import INPUT_SHAPES, ModelConfig, ShapeConfig, TrainConfig
from ..models import model as M
from ..optim import optimizers as opt
from ..sharding import AxisRules


# ---------------------------------------------------------------------------
# Effective decode geometry per shape
# ---------------------------------------------------------------------------


def decode_geometry(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Cache length / window / ring flag for a decode shape.

    long_500k requires sub-quadratic attention: SSM/hybrid state is O(1);
    attention layers fall back to the arch's sliding window, or the
    `swa-variant` window for full-attention archs (DESIGN.md §4).  An arch
    with a window per layer (`layer_windows`) keeps its own, full layers
    included, and takes no override.
    """
    window = 0 if cfg.layer_windows else cfg.sliding_window
    cache_len = shape.seq_len
    ring = False
    variant = "native"
    if shape.name == "long_500k":
        if not (window or cfg.is_attention_free() or cfg.layer_windows):
            window = cfg.long_context_window
            if cfg.family != "hybrid":
                variant = "swa-variant"
        if window:
            cache_len = min(cache_len, window)
            ring = True
        if cfg.is_attention_free():
            cache_len = 1  # no kv cache at all; k_pos degenerates
    return {"window": window, "cache_len": cache_len, "ring": ring,
            "variant": variant}


def memory_len(cfg: ModelConfig) -> int:
    if cfg.family == "audio":
        return cfg.encoder_seq
    if cfg.family == "vlm":
        return cfg.num_image_tokens
    return 0


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, rules: AxisRules, tc: TrainConfig):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    p_shard = jax.tree.map(lambda s: NamedSharding(rules.mesh, s),
                           M.param_specs(cfg, rules),
                           is_leaf=lambda x: isinstance(x, P))

    def _tree_gn(g):
        # NB: no reshape/vdot here — flattening a sharded grad forces an
        # all-gather of the whole tensor; axis-wise sum keeps shards local.
        return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree.leaves(g)))

    def _apply(grads, params, opt_state):
        if tc.optimizer == "adamw":
            return opt.adamw(grads, opt_state, lr=tc.learning_rate,
                             weight_decay=tc.weight_decay, params=params)
        return opt.sgdm(grads, opt_state, lr=tc.learning_rate,
                        momentum=tc.momentum, weight_decay=tc.weight_decay,
                        params=params)

    def train_step(params, opt_state, batch):
        def lf(p, b, tw):
            return M.loss_fn(cfg, p, b, rules=rules, remat=tc.remat,
                             total_weight=tw)

        if tc.accum_steps <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                lf, has_aux=True)(params, batch, None)
            # pin weight grads to the param sharding (FSDP reduce-scatter
            # target) so GSPMD lowers dW as partial-dot + reduce-scatter
            # instead of gathering activations.
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, p_shard)
            updates, opt_state2 = _apply(grads, params, opt_state)
            new_params = opt.apply_updates(params, updates)
            metrics = dict(metrics, grad_norm=_tree_gn(grads))
            return new_params, opt_state2, metrics

        # gradient accumulation: microbatch grads accumulate straight into
        # the (fp32, param-sharded) momentum buffer — no extra grad buffer.
        A = tc.accum_steps
        total_w = jnp.maximum(
            jnp.sum(batch["weights"].astype(jnp.float32)), 1e-9)
        micro = jax.tree.map(
            lambda a: a.reshape((A, a.shape[0] // A) + a.shape[1:]), batch)
        assert tc.optimizer == "sgdm", "accum_steps>1 requires sgdm"
        mu0 = jax.tree.map(lambda m: tc.momentum * m, opt_state.mu)

        def mb(carry, b):
            mu, loss_acc, aux_acc = carry
            (loss, metrics), g = jax.value_and_grad(
                lf, has_aux=True)(params, b, total_w)
            g = jax.tree.map(jax.lax.with_sharding_constraint, g, p_shard)
            mu = jax.tree.map(lambda m, gg: m + gg.astype(jnp.float32), mu, g)
            return (mu, loss_acc + metrics["loss"],
                    aux_acc + metrics["aux_loss"]), None

        (mu, loss, aux), _ = jax.lax.scan(
            mb, (mu0, jnp.float32(0.0), jnp.float32(0.0)), micro)
        g_total = jax.tree.map(lambda a, b: a - b, mu, mu0)
        updates = jax.tree.map(lambda m: -tc.learning_rate * m, mu)
        opt_state2 = opt.OptState(opt_state.step + 1, mu, None)
        new_params = opt.apply_updates(params, updates)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": _tree_gn(g_total)}
        return new_params, opt_state2, metrics

    return train_step


def make_lsgd_train_step(cfg: ModelConfig, rules: AxisRules, tc: TrainConfig):
    """TRUE local SGD (Lin et al. 2018; the paper's DNN algorithm) at pod
    scale: every data shard keeps a full parameter REPLICA, runs H local
    SGD steps on its own chunk-derived microbatches, and the Stich-weighted
    deltas are merged with one psum per iteration — H× less merge traffic
    than mSGD, exactly the paper's communication-efficiency story.

    Requires replicated params (~<=2B at fp32-momentum on 16 GiB chips);
    the big archs use the mSGD special case (H=1) instead — DESIGN.md §4.

    batch: tokens/labels (B, S) with B = n_shards * H * L, weights (B,).
    """
    mesh = rules.mesh
    from jax.sharding import PartitionSpec as P
    data_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    n_shards = 1
    for n in data_axes:
        n_shards *= mesh.shape[n]
    H = tc.local_steps

    def worker(params, momentum, tokens, labels, weights):
        # tokens: (B_loc, S) on this shard; run H local steps of L samples
        B_loc = tokens.shape[0]
        L = B_loc // H
        tok = tokens.reshape(H, L, -1)
        lab = labels.reshape(H, L, -1)
        wgt = weights.reshape(H, L)

        def local_step(p, xs):
            t, l, w = xs
            batch = {"tokens": t, "labels": l, "weights": w}

            def lf(pp):
                # inside shard_map each replica runs UNSHARDED: rules=None
                return M.loss_fn(cfg, pp, batch, rules=None, remat=tc.remat)

            (loss, _), g = jax.value_and_grad(lf, has_aux=True)(p)
            p = jax.tree.map(
                lambda a, b: (a - tc.learning_rate * b).astype(a.dtype), p, g)
            return p, loss

        p_end, losses = jax.lax.scan(local_step, params, (tok, lab, wgt))
        delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32),
                             p_end, params)
        # Stich weighting: this worker's processed-weight fraction
        my_w = jnp.sum(weights)
        total_w = my_w
        for ax in data_axes:
            total_w = jax.lax.psum(total_w, ax)
        frac = my_w / jnp.maximum(total_w, 1e-9)
        merged = jax.tree.map(lambda d: d * frac, delta)
        for ax in data_axes:
            merged = jax.tree.map(lambda d, a=ax: jax.lax.psum(d, a), merged)
        new_mom = jax.tree.map(lambda m, d: tc.momentum * m + d,
                               momentum, merged)
        new_params = jax.tree.map(lambda p, v: (p.astype(jnp.float32) + v
                                                ).astype(p.dtype),
                                  params, new_mom)
        loss = jnp.mean(losses)
        for ax in data_axes:
            loss = jax.lax.pmean(loss, ax)
        return new_params, new_mom, loss

    bspec = P(data_axes if data_axes else None)

    def train_step(params, momentum, batch):
        fn = jax.shard_map(
            worker, mesh=mesh,
            in_specs=(P(), P(), bspec, bspec, bspec),
            out_specs=(P(), P(), P()),
            check_vma=False)
        new_params, new_mom, loss = fn(params, momentum, batch["tokens"],
                                       batch["labels"], batch["weights"])
        return new_params, new_mom, {"loss": loss}

    return train_step


def make_prefill_step(cfg: ModelConfig, rules: AxisRules, *,
                      window: Optional[int] = None):
    def prefill_step(params, batch):
        return M.prefill(cfg, params, batch["tokens"],
                         memory=batch.get("memory"), rules=rules,
                         window=window)
    return prefill_step


def make_serve_step(cfg: ModelConfig, rules: AxisRules, *,
                    window: Optional[int] = None, ring: bool = False):
    def serve_step(params, cache, token, pos):
        return M.decode_step(cfg, params, cache, token, pos, rules=rules,
                             window=window, ring=ring)
    return serve_step


# ---------------------------------------------------------------------------
# Input ShapeDtypeStructs + shardings
# ---------------------------------------------------------------------------


def _batch_spec(rules: AxisRules, B: int) -> P:
    ax = rules.batch
    if ax is None:
        return P()
    n = rules.axis_size(ax)
    if B % n != 0:
        # undivisible tiny batches (long_500k B=1): replicate
        return P()
    return P(ax)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, rules: AxisRules,
                tc: Optional[TrainConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins + NamedShardings for every model input.

    Returns dict with keys:
      kind: train|prefill|decode
      args: tuple of SDS pytrees matching the step signature
      in_shardings / out_shardings: matching pytrees for jax.jit
    """
    mesh = rules.mesh
    dt = jnp.dtype(cfg.dtype)
    B, S = shape.global_batch, shape.seq_len
    bspec = _batch_spec(rules, B)
    ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731

    p_sds = M.param_sds(cfg)
    p_specs = M.param_specs(cfg, rules)
    p_shard = jax.tree.map(ns, p_specs,
                           is_leaf=lambda x: isinstance(x, P))

    mem_len = memory_len(cfg)

    if shape.kind == "train":
        tc = tc or TrainConfig()
        batch_sds = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "weights": jax.ShapeDtypeStruct((B,), jnp.float32),
        }
        batch_shard = {
            "tokens": ns(P(*bspec, None)),
            "labels": ns(P(*bspec, None)),
            "weights": ns(P(*bspec)),
        }
        if mem_len:
            batch_sds["memory"] = jax.ShapeDtypeStruct((B, mem_len, cfg.d_model), dt)
            batch_shard["memory"] = ns(P(*bspec, None, None))
        o_sds = opt.opt_state_sds(p_sds, optimizer=tc.optimizer)
        o_specs = opt.opt_specs(p_specs, optimizer=tc.optimizer)
        o_shard = jax.tree.map(ns, o_specs, is_leaf=lambda x: isinstance(x, P))
        return {
            "kind": "train",
            "args": (p_sds, o_sds, batch_sds),
            "in_shardings": (p_shard, o_shard, batch_shard),
            "out_shardings": (p_shard, o_shard, None),
            "donate_argnums": (0, 1),
            "train_cfg": tc,
        }

    if shape.kind == "prefill":
        batch_sds = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        batch_shard = {"tokens": ns(P(*bspec, None))}
        if mem_len:
            batch_sds["memory"] = jax.ShapeDtypeStruct((B, mem_len, cfg.d_model), dt)
            batch_shard["memory"] = ns(P(*bspec, None, None))
        geo = decode_geometry(cfg, shape)
        return {
            "kind": "prefill",
            "args": (p_sds, batch_sds),
            "in_shardings": (p_shard, batch_shard),
            "out_shardings": None,
            "donate_argnums": (),
            "window": geo["window"] or None,
            "variant": "native",
        }

    # decode
    geo = decode_geometry(cfg, shape)
    c_sds = M.cache_sds(cfg, B, geo["cache_len"], cross_len=mem_len)
    c_specs = M.cache_specs(cfg, rules)
    # drop any cache-dim sharding whose size is not divisible by the mesh
    # axis (tiny batches, 1500-frame cross caches, ring windows, ...)
    c_specs = jax.tree.map(
        lambda spec, sds: rules.guard(spec, sds.shape),
        c_specs, c_sds, is_leaf=lambda x: isinstance(x, P))
    c_shard = jax.tree.map(ns, c_specs, is_leaf=lambda x: isinstance(x, P))
    token_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    return {
        "kind": "decode",
        "args": (p_sds, c_sds, token_sds, pos_sds),
        "in_shardings": (p_shard, c_shard, ns(P(*bspec, None)), ns(P())),
        "out_shardings": None,
        "donate_argnums": (1,),
        "window": geo["window"] or None,
        "ring": geo["ring"],
        "variant": geo["variant"],
    }


def build_step(cfg: ModelConfig, shape: ShapeConfig, rules: AxisRules,
               spec: Dict[str, Any]):
    if spec["kind"] == "train":
        return make_train_step(cfg, rules, spec["train_cfg"])
    if spec["kind"] == "prefill":
        return make_prefill_step(cfg, rules, window=spec.get("window"))
    return make_serve_step(cfg, rules, window=spec.get("window"),
                           ring=spec.get("ring", False))
