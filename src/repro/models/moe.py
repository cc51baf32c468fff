"""Mixture-of-Experts FFN: dropless top-k routing over the experts a chip
holds.

The router scores every one of the config's `num_experts` experts;
softmax over all of them, top-k, and the k weights renormalised to sum to
one.  This chip holds experts [first, first + count) (`cfg.held_experts`,
its share of an expert-parallel deployment; all of them by default) and
computes only its held experts' part of the result: the (token, expert)
rows routed to a held expert are sorted by expert and run through the
grouped Pallas kernel (`kernels.grouped_matmul.expert_matmul`), each
weighted by its gate, and summed back per token.  No row is ever dropped
(there are no capacity buffers); rows routed to experts held elsewhere,
and rows of tokens the caller marks invalid (padding, idle decode slots),
sort last and are not computed.  What the absent experts would add is
left out: this layer's output is the partial sum its experts give.

Returns the output, a Switch-style load-balance aux loss for the
trainer, and the call's counters (`N_STATS` int32).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import grouped_matmul as gm
from .layers import ParamDef

# counters of one layer call, int32: rows routed to held experts (valid
# tokens only), rows the grouped kernel computes (tile padding included),
# held experts with at least one row
N_STATS = 3


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    held = cfg.held_experts()[1]
    expert_parallel = held % 16 == 0  # many experts: shard the expert dim
    if expert_parallel:
        axes3 = ("tensor", "fsdp", None)  # (E, D, F): E -> model
        axes3b = ("tensor", None, "fsdp")  # (E, F, D)
    else:
        axes3 = (None, "fsdp", "tensor")  # (E, D, F): F -> model
        axes3b = (None, "tensor", "fsdp")
    defs = {
        "router": ParamDef((d, e), ("fsdp", None), init="small"),
        "gate": ParamDef((held, d, f), axes3),
        "up": ParamDef((held, d, f), axes3),
        "down": ParamDef((held, f, d), axes3b),
    }
    if cfg.moe_dense_residual:
        fr = cfg.dense_residual_ff or f
        defs["res_gate"] = ParamDef((d, fr), ("fsdp", "tensor"))
        defs["res_up"] = ParamDef((d, fr), ("fsdp", "tensor"))
        defs["res_down"] = ParamDef((fr, d), ("tensor", "fsdp"))
    return defs


def moe_ffn(cfg: ModelConfig, p, x: jax.Array, *,
            valid: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D); valid: optional (B, S) bool, False for tokens whose
    output is never read (their rows are not computed).

    Returns (out (B, S, D), aux_loss scalar, stats (N_STATS,) int32)."""
    from ..kernels.ops import interpret_mode

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    first, held = cfg.held_experts()
    T = B * S
    with jax.named_scope("moe"):
        xt = x.reshape(T, D)
        # float32 scores: a bfloat16 rounding would flip near-tied choices
        logits = jnp.einsum("td,de->te", xt, p["router"],
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, K)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        # Switch-style aux load-balance loss over all E router outputs
        density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32),
                           axis=0)
        aux = E * jnp.sum(density * jnp.mean(probs, axis=0))

        # rows (token, choice) by held expert; the rest sort last
        local = top_e.reshape(-1) - first
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine &= jnp.repeat(valid.reshape(T), K)
        group = jnp.where(mine, local, held)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1).astype(jnp.int32)
        rows = jnp.take(jnp.repeat(xt, K, axis=0), order, axis=0,
                        unique_indices=True)  # (T*K, D)

        # the expert weights come whole, (L, held, ...), with the layer
        # index under "layer" (`model._scan_layers` passes them so), or as
        # one layer's (held, ...)
        def matmul(lhs, w):
            if "layer" not in p:
                w = w[None]
            return gm.expert_matmul(lhs, w, sizes, p.get("layer", 0),
                                    interpret=interpret_mode())

        h = matmul(rows, p["gate"])
        u = matmul(rows, p["up"])
        y = matmul(jax.nn.silu(h) * u, p["down"])  # (T*K, D), 0 past held

        # back to (token, choice) order, each row weighted by its gate
        slot = jnp.zeros_like(order).at[order].set(  # sorted position of
            jnp.arange(T * K, dtype=order.dtype))    # each (t, k) row
        w = jnp.where(mine, top_w.reshape(-1), 0.0)
        out = jnp.sum((jnp.take(y, slot, axis=0).astype(jnp.float32)
                       * w[:, None]).reshape(T, K, D), axis=1)

        if cfg.moe_dense_residual:
            h = jax.nn.silu(jnp.einsum("td,df->tf", xt, p["res_gate"]))
            h = h * jnp.einsum("td,df->tf", xt, p["res_up"])
            out = out + jnp.einsum("tf,fd->td", h,
                                   p["res_down"]).astype(out.dtype)

        stats = jnp.stack([sizes[:held].sum(),
                           gm.rows_computed(sizes, T * K),
                           jnp.sum(sizes[:held] > 0)]).astype(jnp.int32)
    return out.reshape(B, S, D).astype(x.dtype), aux, stats
