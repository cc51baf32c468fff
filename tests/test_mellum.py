"""Mellum 2 (per-layer window and full attention, YaRN on the full layers,
dropless experts of which the chip holds a share) against the plain
float32 reference of the benchmark (`bench/reference_mellum.py`), at a
small size on the CPU, with seeded random weights.

The program runs in float32 here, so program and reference differ only in
the order of their sums: `TOL` (absolute, on logits of spread ~0.85) is
about a hundred times the largest such difference seen (1.3e-6), and a
ten-thousandth of what leaving out one token's routed rows moves a layer's
output (0.94, `test_a_dropped_row_fails_the_comparison`).
"""
from __future__ import annotations

import dataclasses
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import common, reference_mellum as ref  # noqa: E402
from bench.entries import serve_moe  # noqa: E402
from repro.configs import ModelConfig, get_config, smoke_variant  # noqa: E402
from repro.models import model as M, moe  # noqa: E402
from repro.models.layers import yarn_frequencies  # noqa: E402

SEED = 2**31 + 1515
TOL = 1e-4


def tiny_model() -> dict:
    """Mellum's configuration file at a small size: one whole period of
    layers (3 window, 1 full), a 16-token window, 8 experts of which 4 are
    held, top-2; weights five times the published spread so that attention
    and routing are far from uniform."""
    m = common.load_json(ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
    L = 4
    m.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, moe_intermediate_size=48, num_hidden_layers=L,
             layer_types=m["layer_types"][:L],
             mlp_layer_types=m["mlp_layer_types"][:L], vocab_size=256,
             num_experts=4, num_experts_per_tok=2, sliding_window=16,
             initializer_range=0.1, dtype="float32",
             published={"num_experts": 8})
    return m


@pytest.fixture(scope="module")
def model():
    m = tiny_model()
    cfg = serve_moe.program_config(m)
    return m, cfg, serve_moe.for_program(m, SEED, cfg), ref.published(
        m, SEED, jnp.float32)


def ref_logits(m, w, tokens) -> np.ndarray:
    fwd = ref.Forward(m, w, pad=64)
    x = fwd.hidden(np.asarray(tokens))[: len(tokens)]
    with jax.default_matmul_precision("highest"):
        h = ref.rms_norm(x, w["final_ln"], m["rms_norm_eps"])
        return np.asarray(h @ w["head"].T)


def test_config_is_the_published_one():
    cfg = get_config("mellum2-12b-a2.5b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.kv_heads(),
            cfg.head_dim_(), cfg.d_ff, cfg.vocab_size) == (
                28, 2304, 32, 4, 128, 896, 98304)
    assert (cfg.num_experts, cfg.experts_per_token,
            cfg.held_experts()) == (64, 8, (0, 16))
    assert cfg.layer_windows == (1024, 1024, 1024, 0)
    assert not cfg.tie_embeddings and cfg.rope_yarn.factor == 16
    # the benchmark's file maps onto exactly the registered config
    m = common.load_json(ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
    assert serve_moe.program_config(m) == cfg
    smoke = smoke_variant(cfg)
    assert smoke.num_layers == 4 and smoke.num_experts == 4
    assert smoke.held_experts() == (0, 2)
    assert "moe_capacity_factor" not in ModelConfig.__dataclass_fields__


def test_yarn_frequencies_match_closed_form():
    """YaRN from the config's numbers (arXiv:2309.00071, eq. 23-25 as the
    Hugging Face rule truncates them): dims whose wavelength fits fewer
    than beta_slow turns of the original context are divided by the
    factor, more than beta_fast kept, a linear ramp between."""
    m = common.load_json(ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
    p = m["rope_parameters"]["full_attention"]
    hd, theta, s = m["head_dim"], p["rope_theta"], p["factor"]
    orig = p["original_max_position_embeddings"]
    base = [theta ** (-2 * i / hd) for i in range(hd // 2)]
    # dim d turns orig / (2 pi theta^(2d/hd)) times over the context
    d_of = lambda r: hd * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
    lo, hi = math.floor(d_of(p["beta_fast"])), math.ceil(d_of(p["beta_slow"]))
    want = []
    for i, f in enumerate(base):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(f / s * ramp + f * (1 - ramp))
    cfg = get_config("mellum2-12b-a2.5b")
    got, scale = yarn_frequencies(hd, cfg.rope_theta, cfg.rope_yarn)
    np.testing.assert_allclose(got, want, rtol=1e-6)  # float32 rounding
    assert (lo, hi) == (18, 35)
    assert got[0] == 1.0 and got[-1] == pytest.approx(base[-1] / s, rel=1e-6)
    # the attention factor is the YaRN default, 0.1 ln(s) + 1
    assert scale == pytest.approx(0.1 * math.log(s) + 1, rel=1e-12)
    inv, scale_ref = ref.rope_parameters(m, "full_attention")
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert scale_ref == scale
    inv, one = ref.rope_parameters(m, "sliding_attention")
    np.testing.assert_allclose(inv, base, rtol=1e-6)
    assert one == 1.0


def test_forward_matches_reference(model):
    m, cfg, params, w = model
    toks = np.asarray(jax.random.randint(jax.random.key(1), (2, 40), 0,
                                         m["vocab_size"]))
    logits, _ = M.forward(cfg, params, jnp.asarray(toks), rules=None,
                          remat=True)
    for b in range(2):
        want = ref_logits(m, w, toks[b])
        assert np.abs(np.asarray(logits[b]) - want).max() < TOL


def test_prefill_then_paged_decode_matches_reference(model):
    """Whole-prompt prefill, its K/V scattered into pages, then paged decode
    steps through the Pallas kernel (interpreted), one token a step: every
    step's logits are the reference's full forward pass at that position.
    The prompts are longer than the window, so window layers mask keys
    that full layers attend."""
    m, cfg, params, w = model
    B, bucket, ps, P, steps = 2, 48, 8, 8, 6
    lens = np.array([40, 33])
    seq = np.asarray(jax.random.randint(jax.random.key(2), (B, 48), 0,
                                        m["vocab_size"]))
    toks = np.where(np.arange(bucket)[None] < lens[:, None], seq, 0)
    last, cache, stats = M.prefill(
        cfg, params, jnp.asarray(toks), rules=None, remat=False,
        cache_len=bucket, true_len=jnp.asarray(lens), stats=True)
    # every valid prompt token sends top-2 rows; the held share gets some
    assert 0 < int(stats[0]) <= 2 * lens.sum() * cfg.num_layers
    assert int(stats[1]) >= int(stats[0])

    L, KV, hd = cfg.num_layers, cfg.kv_heads(), cfg.head_dim_()
    pool = M.init_paged_cache(cfg, 1 + B * P, ps)["blocks"]
    table = 1 + np.arange(B * P).reshape(B, P)
    ids = jnp.asarray(table[:, : bucket // ps].reshape(-1))
    pool = {k: pool[k].at[:, ids].set(
        cache["blocks"][k].reshape(L, B * bucket // ps, ps, KV * hd))
        for k in ("k", "v")}
    got = {b: [np.asarray(last[b, 0])] for b in range(B)}
    for t in range(steps):
        pos = lens + t
        logits, new, _ = M.paged_step(
            cfg, params, {"blocks": pool},
            jnp.asarray(seq[np.arange(B), pos][:, None]), jnp.asarray(pos),
            jnp.asarray(table), jnp.asarray(pos + 1), rules=None,
            impl="pallas", stats=True)
        pool = new["blocks"]
        for b in range(B):
            got[b].append(np.asarray(logits[b, 0]))
    for b in range(B):
        n = lens[b]
        want = ref_logits(m, w, seq[b, : n + steps])[n - 1: n + steps]
        assert np.abs(np.stack(got[b]) - want).max() < TOL


def _layer_inputs(m, cfg, seed):
    """A normed (1, T, D) input and a whole layer's router and experts (all
    of the published count), float32."""
    k = jax.random.split(jax.random.key(seed), 5)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    x = jax.random.normal(k[0], (1, 24, D))
    full = {"router": 0.5 * jax.random.normal(k[1], (D, E)),
            "gate": 0.1 * jax.random.normal(k[2], (E, D, F)),
            "up": 0.1 * jax.random.normal(k[3], (E, D, F)),
            "down": 0.1 * jax.random.normal(k[4], (E, F, D))}
    return x, full


def _held(full, first, count):
    return {"router": full["router"],
            **{n: full[n][first:first + count] for n in ("gate", "up", "down")}}


def _share(cfg, full, first, count):
    return (dataclasses.replace(cfg, experts_held=(first, count)),
            _held(full, first, count))


def _ref_layer(m, x, full, first, held):
    mm = dict(m, experts_held_from=first, num_experts=held)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.experts(mm, False, x[0],
                                      _held(full, first, held)))


def test_expert_shares_add_up_to_the_whole_layer(model):
    """Two chips' shares of the layer (experts 0-3 and 4-7 of 8): the
    partial outputs each computes add up to the reference's whole layer."""
    m, cfg, _, _ = model
    x, full = _layer_inputs(m, cfg, 7)
    E = cfg.num_experts
    parts = []
    for first in (0, E // 2):
        c, p = _share(cfg, full, first, E // 2)
        out, _, stats = moe.moe_ffn(c, p, x)
        parts.append(np.asarray(out[0]))
        np.testing.assert_allclose(parts[-1], _ref_layer(m, x, full, first,
                                                         E // 2), atol=1e-5)
    # all 24 x 2 rows are routed to one share or the other
    whole = _ref_layer(m, x, full, 0, E)
    assert np.abs(parts[0] + parts[1] - whole).max() < 1e-5


def test_a_dropped_row_fails_the_comparison(model):
    """A layer that leaves out one token's routed rows (as a capacity
    buffer drops overflow) misses the reference by far more than TOL."""
    m, cfg, _, _ = model
    x, full = _layer_inputs(m, cfg, 8)
    c, p = _share(cfg, full, 0, cfg.num_experts)
    want = _ref_layer(m, x, full, 0, cfg.num_experts)
    out, _, _ = moe.moe_ffn(c, p, x)
    assert np.abs(np.asarray(out[0]) - want).max() < 1e-5
    keep = jnp.ones((1, 24), bool).at[0, 5].set(False)
    dropped, _, stats = moe.moe_ffn(c, p, x, valid=keep)
    assert int(stats[0]) == 23 * cfg.experts_per_token
    assert np.abs(np.asarray(dropped[0]) - want).max() > 100 * TOL


def _serve(kv_layout, **kw):
    """Mellum's smoke variant (4 layers, a 16-token window) through
    ServeEngine on prompts longer than the window; the engine too."""
    from repro.serve import ServeEngine, synthetic_requests

    cfg = smoke_variant(get_config("mellum2-12b-a2.5b"))
    rng = np.random.default_rng(3)
    reqs = synthetic_requests(6, vocab_size=cfg.vocab_size,
                              arrivals=np.zeros(6), prompt_len=(20, 40),
                              max_new_tokens=(6, 10), rng=rng)
    eng = ServeEngine(cfg, capacity=4, cache_len=64, prefill_bucket=16,
                      kv_layout=kv_layout, page_size=8, seed=0, **kw)
    metrics = eng.run(reqs)
    return eng, metrics, {r.rid: list(r.generated) for r in metrics.requests}


@pytest.fixture(scope="module")
def paged_pallas():
    return _serve("paged", paged_impl="pallas", chunked_prefill=False,
                  overlap=True)


@pytest.mark.parametrize("variant", [
    dict(kv_layout="flat"),
    dict(kv_layout="paged", paged_impl="xla", chunked_prefill=True),
    dict(kv_layout="paged", paged_impl="pallas", chunked_prefill=False,
         spec="ngram"),
])
def test_every_serving_path_serves_the_same_tokens(paged_pallas, variant):
    """Flat decode, chunked paged prefill and speculative verify give the
    streams of the cell's path (paged, Pallas, whole-prompt prefill): each
    runs every layer with its own window and RoPE."""
    _, _, want = paged_pallas
    _, _, got = _serve(**variant)
    assert got == want


def test_engine_counts_expert_rows_and_keys(paged_pallas):
    eng, metrics, _ = paged_pallas
    cfg = eng.cfg
    ticks = metrics.ticks
    routed = sum(t.moe_rows_routed for t in ticks)
    assert 0 < routed <= sum(t.moe_rows_computed for t in ticks)
    dec = [t for t in ticks if t.tokens_emitted]
    # a decode step routes top-2 rows of each live slot on each layer, to
    # 2 of the 4 experts here: at most all of them held
    for t in dec:
        assert t.moe_decode_rows_routed <= (2 * t.tokens_emitted
                                            * cfg.num_layers)
        assert t.moe_decode_experts_touched <= 2 * cfg.num_layers
    assert sum(t.moe_decode_experts_touched for t in dec) > 0
    # keys: 3 window layers see at most 16 keys a query, the full one all
    assert all(t.attn_keys_window <= 3 * 16 * t.tokens_emitted for t in dec)
    assert sum(t.attn_keys_full for t in dec) > sum(
        t.attn_keys_window for t in dec) / 3
    reg = metrics.to_registry()
    assert reg.counter("serve.moe_rows_routed").value == routed
    assert reg.counter("serve.attn_keys_full").value == sum(
        t.attn_keys_full for t in ticks)
