"""jit'd wrappers: model-layout adapters + TPU/interpret dispatch.

`interpret_mode()` is the one place that decides how the Pallas kernels run:
compiled on a TPU, interpreted on the CPU (validation), and an error on any
other platform, so a program that misses its chip stops instead of silently
interpreting.  The model code can also bypass kernels entirely
(models/attention.py XLA path) — that is what the dry-run lowers, since
Pallas custom-calls don't lower on the CPU SPMD backend.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import chunk_reduce, flash_attention as fa, ref, scd


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on the default device:
    True on "cpu", False on "tpu", ValueError on anything else."""
    platform = jax.devices()[0].platform
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(f"Pallas kernels run compiled on 'tpu' or interpreted "
                     f"on 'cpu'; got platform {platform!r}")


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Model-layout flash attention.

    q: (B, S, KV, G, hd); k, v: (B, S, KV, hd) -> (B, S, KV, G, hd).
    """
    B, S, KV, G, hd = q.shape
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * KV * G, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    of = fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                            block_q=min(block_q, S), block_k=min(block_k, S),
                            group_size=G, interpret=interpret_mode())
    return of.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4)


def scd_local_pass(x, y, alpha, w, mask, lam_n, sigma
                   ) -> Tuple[jax.Array, jax.Array]:
    """CoCoA local SCD pass: x (K,M,F), returns (v_end (K,F), da (K,M))."""
    return scd.scd_pass(x, y, alpha, w, mask, lam_n, sigma,
                        interpret=interpret_mode())


def merge_updates(updates: jax.Array, weights: jax.Array) -> jax.Array:
    """Weighted uni-task merge: (K, N) x (K,) -> (N,)."""
    return chunk_reduce.weighted_merge(updates, weights,
                                       interpret=interpret_mode())


def merge_pytree(deltas, weights):
    """Weighted merge of a pytree of stacked (K, ...) worker deltas."""
    leaves, treedef = jax.tree.flatten(deltas)
    K = leaves[0].shape[0]
    flat = jnp.concatenate([l.reshape(K, -1) for l in leaves], axis=1)
    merged = merge_updates(flat, weights)
    out, off = [], 0
    for l in leaves:
        n = int(l[0].size)
        out.append(merged[off:off + n].reshape(l.shape[1:]))
        off += n
    return jax.tree.unflatten(treedef, out)
