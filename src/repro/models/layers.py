"""Shared layers + declarative parameter system.

Parameters are declared as ``ParamDef(shape, logical_axes)`` trees; the same
declaration yields (a) randomly-initialized arrays, (b) PartitionSpecs for
pjit in_shardings, and (c) ShapeDtypeStructs for dry-run lowering.  Stacked
(scanned) layers add a leading layer dim with logical axis None.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding import AxisRules


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axes, same length as shape
    init: str = "normal"  # normal | zeros | ones | small
    scale: float = 1.0

    def stacked(self, n: int) -> "ParamDef":
        return ParamDef((n,) + self.shape, (None,) + self.axes, self.init, self.scale)


def _fan_in(shape: Tuple[int, ...]) -> int:
    # all dims except the last are treated as fan-in (works for our einsums)
    return max(1, int(np.prod(shape[:-1])))


def init_tree(defs: Any, key: jax.Array, dtype) -> Any:
    """Instantiate a ParamDef tree into arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    out = []
    for d, k in zip(leaves, keys):
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dtype))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dtype))
        else:
            std = d.scale / math.sqrt(_fan_in(d.shape))
            if d.init == "small":
                std = d.scale * 0.02
            out.append((jax.random.normal(k, d.shape) * std).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def spec_tree(defs: Any, rules: AxisRules) -> Any:
    """ParamDef tree -> PartitionSpec tree (fsdp backs off on non-divisible dims)."""
    def to_spec(d: ParamDef):
        return rules.fsdp_spec(*d.axes, dim_sizes=d.shape)

    return jax.tree.map(to_spec, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def sds_tree(defs: Any, dtype) -> Any:
    """ParamDef tree -> ShapeDtypeStruct tree (dry-run, no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


# ---------------------------------------------------------------------------
# Core math layers (functional)
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


def yarn_frequencies(hd: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (hd/2,), cos/sin scale) of YaRN RoPE, as the
    Hugging Face `rope_type: yarn` computes them (correction range
    truncated to whole dims)."""
    half = hd // 2
    base = theta ** (np.arange(half, dtype=np.float64) / half)

    def dim_of(rotations):  # dim whose wavelength fits `rotations` turns
        return (hd * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim_of(yarn.beta_slow)), hd - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: extrapolate (the base frequency), 0: interpolate
    inv = (1.0 / (yarn.factor * base)) * (1.0 - keep) + (1.0 / base) * keep
    return inv.astype(np.float32), float(yarn.attention_factor)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn=None) -> jax.Array:
    """Rotary embedding. x: (..., S, ..., hd); positions: (..., S) broadcastable.

    We apply over the last dim with positions broadcast from axis carrying S.
    x shape convention here: (B, S, KV, G, hd) or (B, S, KV, hd); positions (B, S).
    yarn: optional `configs.YarnRope` scaling of the frequencies.
    """
    hd = x.shape[-1]
    half = hd // 2
    if yarn is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs, scale = yarn_frequencies(hd, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    # insert head-ish axes between S and hd so ang broadcasts against x
    for _ in range(x.ndim - 3):
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
           rules: Optional[AxisRules] = None) -> jax.Array:
    from ..sharding import use_weight
    w_gate = use_weight(w_gate, rules, "fsdp", "tensor")
    w_up = use_weight(w_up, rules, "fsdp", "tensor")
    w_down = use_weight(w_down, rules, "tensor", "fsdp")
    h = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    h = h * jnp.einsum("...d,df->...f", x, w_up)
    if rules is not None and h.ndim == 3:
        # (B, S, F): batch stays batch-sharded, F tensor-sharded
        h = jax.lax.with_sharding_constraint(
            h, rules.sharding("batch", None, "tensor"))
    return jnp.einsum("...f,fd->...d", h, w_down)


def mlp_defs(d_model: int, d_ff: int) -> Dict[str, ParamDef]:
    return {
        "gate": ParamDef((d_model, d_ff), ("fsdp", "tensor")),
        "up": ParamDef((d_model, d_ff), ("fsdp", "tensor")),
        "down": ParamDef((d_ff, d_model), ("tensor", "fsdp")),
    }


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Stable CE over a (possibly vocab-sharded) last dim. Returns per-token loss."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked
