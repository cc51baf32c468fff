"""Plain reference of the Mellum 2 decoder (`model_type: mellum`), in float32
at full matmul precision, one layer at a time, with its own weights from
the seed.  It reads the configuration file's published keys and nothing
of the program.

A layer, as the published config describes it: RMSNorm; grouped-query
attention with a head size of its own (`head_dim`), RoPE by the layer's
type (`rope_parameters`: default on `sliding_attention` layers, YaRN with
its attention factor on `full_attention` ones), a causal mask that a
sliding layer narrows to its last `sliding_window` keys; RMSNorm; the
sparse MLP: softmax over all the router's outputs, the top
`num_experts_per_tok`, renormalised (`norm_topk_prob`), and each chosen
expert's SwiGLU output weighted by its gate.  Then a final RMSNorm and
the untied head.

The chip holds experts [experts_held_from, + num_experts) of the
published count, and so does this reference: the router scores every
expert, and the layer adds the part of the result its held experts give
(each computed on every token, its gate zero where it was not chosen).
What the other experts would add is left out, here as in the program.
Departures from the published model: no MTP head (it takes no part in
the forward pass), and random weights.

`quant=True` computes every matmul operand, router included, in float8
e4m3 (`reference._fp8`): the precision below the configuration's bfloat16,
the control that the comparison has to reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import jax_key
from bench.reference import _q, _qw, rms_norm, served_gaps  # noqa: F401
from bench.weights import NORM_JITTER

F32 = jnp.float32
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router", "gate", "up",
              "down")


def shapes(m: dict) -> dict:
    L, D, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    E, F = m["published"]["num_experts"], m["moe_intermediate_size"]
    held = m["num_experts"]
    return {"embed": (V, D), "head": (V, D), "final_ln": (D,),
            "ln1": (L, D), "ln2": (L, D), "wq": (L, D, H, hd),
            "wk": (L, D, KV, hd), "wv": (L, D, KV, hd), "wo": (L, H, hd, D),
            "router": (L, D, E), "gate": (L, held, D, F),
            "up": (L, held, D, F), "down": (L, held, F, D)}


def _published(m: dict, dtype, key):
    std = m["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        k = jax.random.fold_in(key, i)

        def draw(k, shape=shape, name=name):
            z = jax.random.normal(k, shape, F32)
            return (1.0 + NORM_JITTER * z if "ln" in name else std * z).astype(dtype)

        if len(shape) > 2:  # one layer at a time bounds the float32 draw
            out[name] = jax.lax.map(
                lambda j, k=k, draw=draw, shape=shape: draw(
                    jax.random.fold_in(k, j), shape[1:]),
                jnp.arange(shape[0]))
        else:
            out[name] = draw(k)
    return out


def published(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The held share's weights at the published shapes, bfloat16 as
    served, made on the device from the seed."""
    return jax.jit(functools.partial(_published, m, dtype))(
        jax_key(seed, "weights"))


def rope_parameters(m: dict, layer_type: str) -> tuple:
    """(inverse frequencies (head_dim/2,), cos/sin factor) of a layer type,
    from its `rope_parameters` section (Hugging Face's default and yarn
    rules, correction range truncated to whole dims)."""
    rp = m["rope_parameters"][layer_type]
    hd, theta = m["head_dim"], float(rp["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rp["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    assert rp["rope_type"] == "yarn", rp
    orig = rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    extrapolate = 1.0 - np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
    inv = inv / rp["factor"] * (1 - extrapolate) + inv * extrapolate
    return inv.astype(np.float32), float(rp["attention_factor"])


def rope(x, pos, inv, scale):
    """Rotate-half RoPE.  x: (S, heads, hd); pos: (S,)."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(m: dict, layer_type: str, quant: bool, h, lw, pos,
              q_block: int = 512):
    """The attention half of a layer over a whole sequence, h normed."""
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    window = m["sliding_window"] if layer_type == "sliding_attention" else 0
    inv, scale = rope_parameters(m, layer_type)
    S = h.shape[0]
    g = H // KV
    q = jnp.einsum("sd,dhe->she", h, _qw(lw["wq"], quant))
    k = jnp.einsum("sd,dke->ske", h, _qw(lw["wk"], quant))
    v = jnp.einsum("sd,dke->ske", h, _qw(lw["wv"], quant))
    q = rope(q, pos, inv, scale).reshape(S, KV, g, hd) / math.sqrt(hd)
    k = _q(rope(k, pos, inv, scale), quant)
    v = _q(v, quant)
    ctx = []
    for s0 in range(0, S, q_block):  # query blocks bound the score memory
        d = pos[s0:s0 + q_block, None] - pos[None, :]
        ok = d >= 0
        if window:
            ok &= d < window
        sc = jnp.einsum("qkgh,skh->kgqs", q[s0:s0 + q_block], k)
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        ctx.append(jnp.einsum("kgqs,skh->qkgh", p, v).reshape(-1, H, hd))
    ctx = _q(jnp.concatenate(ctx, 0), quant)
    return jnp.einsum("she,hed->sd", ctx, _qw(lw["wo"], quant))


def experts(m: dict, quant: bool, h, lw):
    """The held experts' part of the sparse MLP's output, h normed."""
    K = m["num_experts_per_tok"]
    first, held = m["experts_held_from"], m["num_experts"]
    probs = jax.nn.softmax(h @ _qw(lw["router"], quant), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    if m["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    ids = first + jnp.arange(held)
    gates = jnp.sum(jnp.where(top_e[:, :, None] == ids, top_w[:, :, None], 0.0),
                    axis=1)  # (S, held): zero where not chosen

    def one(out, e):
        gate, up, down, g = e
        a = jax.nn.silu(h @ _qw(gate, quant)) * (h @ _qw(up, quant))
        return out + g[:, None] * (_q(a, quant) @ _qw(down, quant)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (lw["gate"], lw["up"], lw["down"], gates.T))
    return out


def layer(m: dict, layer_type: str, quant: bool, x, lw, pos):
    """One decoder layer over a whole sequence.  x: (S, D) float32."""
    eps = m["rms_norm_eps"]
    x = x + attention(m, layer_type, quant,
                      _q(rms_norm(x, lw["ln1"], eps), quant), lw, pos)
    return x + experts(m, quant, _q(rms_norm(x, lw["ln2"], eps), quant), lw)


def _stats(m, quant, x, rows, picks, final_ln, head):
    h = _q(rms_norm(x[rows], final_ln, m["rms_norm_eps"]), quant)
    logits = h @ _qw(head, quant).T
    return (logits.max(-1), jnp.take_along_axis(logits, picks[:, None], -1)[:, 0],
            logits.argmax(-1).astype(jnp.int32))


class Forward:
    """Whole sequences, one layer at a time (each layer's weights are cast to
    float32 only while it runs).  Sequences are padded to a multiple of
    `pad` tokens at the end, which no real position attends.  The interface
    of `reference.Forward`, so `served_gaps` reads either."""

    def __init__(self, m: dict, w: dict, quant: bool = False, pad: int = 1024):
        self.m, self.w, self.quant, self.pad = m, w, quant, pad
        self._layer = {t: jax.jit(functools.partial(layer, m, t, quant))
                       for t in set(m["layer_types"])}
        self._stats = jax.jit(functools.partial(_stats, m, quant))

    def hidden(self, tokens: np.ndarray):
        n = len(tokens)
        S = -(-n // self.pad) * self.pad
        toks = np.zeros(S, np.int32)
        toks[:n] = tokens
        w = self.w
        with jax.default_matmul_precision("highest"):
            x = w["embed"][jnp.asarray(toks)].astype(F32)
            pos = jnp.arange(S, dtype=jnp.int32)
            for i, t in enumerate(self.m["layer_types"]):
                lw = {k: w[k][i].astype(F32) for k in LAYER_KEYS}
                x = self._layer[t](x, lw, pos)
        return x

    def stats(self, x, rows: np.ndarray, picks: np.ndarray) -> tuple:
        """At each of `rows`: (best logit, logit of `picks`, argmax)."""
        S = x.shape[0]
        r = np.zeros(S, np.int32)
        p = np.zeros(S, np.int32)
        r[:len(rows)], p[:len(rows)] = rows, picks
        with jax.default_matmul_precision("highest"):
            out = self._stats(x, jnp.asarray(r), jnp.asarray(p),
                              self.w["final_ln"].astype(F32),
                              self.w["head"].astype(F32))
        return tuple(np.asarray(o)[:len(rows)] for o in out)
