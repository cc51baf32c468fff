"""Serving cells of Mellum-style sparse models (`model_type: mellum`):
`serve.run`, the serving entry, with this family's program configuration,
weights and reference in place of the dense decoder's.

`serve.run` reads the model through three names of its module:
`common.program_config`, `weights` (`for_program`, `published`) and
`reference` (`Forward`, `served_gaps`).  `run` binds them to the
functions below for the run and restores them after, so the traffic,
warm-up, window, metrics and checks are the serving entry's own.

The configuration file's `num_experts` is the count this chip holds,
from `experts_held_from`; the router scores `published.num_experts`.
"""
from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp

from bench import common, reference_mellum, weights
from bench.entries import serve


def _period(seq: list) -> list:
    """The shortest prefix that repeats to give `seq`."""
    for p in range(1, len(seq) + 1):
        if len(seq) % p == 0 and seq == seq[:p] * (len(seq) // p):
            return seq[:p]
    return seq


def program_config(m: dict):
    """The program's model configuration at exactly the sizes of the
    configuration file."""
    common.src_path()
    from repro.configs import YarnRope, get_config

    unsupported = {
        "attention_bias": m["attention_bias"],
        "hidden_act": m["hidden_act"] != "silu",
        "norm_topk_prob": not m["norm_topk_prob"],
        "mlp_layer_types": set(m["mlp_layer_types"]) != {"sparse"},
    }
    if any(unsupported.values()):
        raise SystemExit(f"bench: the program has no {unsupported}")
    rp = m["rope_parameters"]
    full, slide = rp["full_attention"], rp["sliding_attention"]
    if (full["rope_type"], slide["rope_type"]) != ("yarn", "default") or (
            full["rope_theta"] != slide["rope_theta"]):
        raise SystemExit(f"bench: the program has no RoPE of the form {rp}")
    windows = [m["sliding_window"] if t == "sliding_attention" else 0
               for t in m["layer_types"]]
    first = m["experts_held_from"]
    return dataclasses.replace(
        get_config(m["program_arch"]),
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["moe_intermediate_size"], vocab_size=m["vocab_size"],
        sliding_window=0, layer_windows=tuple(_period(windows)),
        rope_theta=float(full["rope_theta"]),
        rope_yarn=YarnRope(
            factor=float(full["factor"]),
            original_max_position=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        tie_embeddings=m["tie_word_embeddings"],
        num_experts=m["published"]["num_experts"],
        experts_per_token=m["num_experts_per_tok"],
        experts_held=(first, m["num_experts"]), dtype=m["dtype"])


def for_program(m: dict, seed: int, program_cfg, sharding=None):
    """The program's parameter tree filled with `reference_mellum.published`
    numbers (padded query heads, if any, hold zeros)."""
    from repro.models import attention, model as M

    kind, hp, g_pad = attention.head_layout(program_cfg)
    idx = jnp.asarray(weights.real_heads(m, kind, g_pad))
    dtype = jnp.dtype(program_cfg.dtype)

    def build(key):
        w = reference_mellum._published(m, dtype, key)
        L, D, H, hd = w["wq"].shape
        wq = jnp.zeros((L, D, hp, hd), dtype).at[:, :, idx].set(w["wq"])
        wo = jnp.zeros((L, hp, hd, D), dtype).at[:, idx].set(w["wo"])
        return {"embed": w["embed"], "head": w["head"],
                "final_ln": w["final_ln"],
                "blocks": {"ln1": w["ln1"], "ln2": w["ln2"],
                           "attn": {"wq": wq, "wk": w["wk"], "wv": w["wv"],
                                    "wo": wo},
                           "moe": {k: w[k] for k in ("router", "gate", "up",
                                                     "down")}}}

    want = M.param_sds(program_cfg)
    got = jax.eval_shape(build, jax.random.key(0))
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.map(lambda a: (a.shape, a.dtype), want)
            != jax.tree.map(lambda a: (a.shape, a.dtype), got)):
        raise SystemExit("bench: the program's parameter tree is not the "
                         "one the benchmark fills")
    return jax.jit(build, out_shardings=sharding)(
        common.jax_key(seed, "weights"))


def run(ctx) -> dict:
    bound = {
        "common": types.SimpleNamespace(**{**vars(common),
                                           "program_config": program_config}),
        "weights": types.SimpleNamespace(
            for_program=for_program, published=reference_mellum.published),
        "reference": reference_mellum,
    }
    saved = {name: getattr(serve, name) for name in bound}
    for name, mod in bound.items():
        setattr(serve, name, mod)
    try:
        return serve.run(ctx)
    finally:
        for name, mod in saved.items():
            setattr(serve, name, mod)
