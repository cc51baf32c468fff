"""Launcher-layer tests: step builders, input specs, lSGD shard_map step,
decode geometry policy, head layouts, sharding regimes."""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import set_mesh

from repro.configs import INPUT_SHAPES, TrainConfig, get_config, list_archs, smoke_variant
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.attention import head_layout, head_maps
from repro.optim import init_opt_state
from repro.sharding import AxisRules

ARCHS = [a for a in list_archs() if not a.startswith("chicle")]


def test_head_layouts_are_16_aligned_and_exact():
    for arch in ARCHS:
        cfg = get_config(arch)
        if cfg.is_attention_free():
            continue
        kind, hp, g_pad = head_layout(cfg)
        assert hp % 16 == 0, arch
        idx, mask = head_maps(cfg)
        # exactly num_heads real heads, each mapped to a valid kv head
        assert int(mask.sum()) == cfg.num_heads, arch
        assert int(idx.max()) < cfg.kv_heads(), arch
        # every kv head serves the same number of REAL q heads (GQA exact)
        g = cfg.num_heads // cfg.kv_heads()
        counts = np.bincount(np.asarray(idx)[np.asarray(mask)],
                             minlength=cfg.kv_heads())
        assert (counts == g).all(), (arch, counts)


def test_decode_geometry_long_context_policy():
    # SSM: no kv cache
    geo = steps.decode_geometry(get_config("rwkv6-1.6b"),
                                INPUT_SHAPES["long_500k"])
    assert geo["cache_len"] == 1
    # native SWA arch keeps its own window
    geo = steps.decode_geometry(get_config("h2o-danube-1.8b"),
                                INPUT_SHAPES["long_500k"])
    assert geo["window"] == 4096 and geo["ring"] and geo["variant"] == "native"
    # full-attention arch gets the swa-variant
    geo = steps.decode_geometry(get_config("qwen3-4b"),
                                INPUT_SHAPES["long_500k"])
    assert geo["variant"] == "swa-variant" and geo["cache_len"] == 4096
    # decode_32k keeps the full cache
    geo = steps.decode_geometry(get_config("qwen3-4b"),
                                INPUT_SHAPES["decode_32k"])
    assert geo["cache_len"] == 32768 and not geo["ring"]


@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_build_for_every_arch(shape_name):
    """Spec building (shapes+shardings) must succeed for all 40 combos —
    the cheap half of the dry-run, runnable on 1 device."""
    mesh = make_host_mesh()
    rules = AxisRules(mesh)
    shape = INPUT_SHAPES[shape_name]
    for arch in ARCHS:
        cfg = get_config(arch)
        spec = steps.input_specs(cfg, shape, rules)
        assert spec["kind"] == shape.kind
        args = jax.tree.leaves(spec["args"])
        assert all(isinstance(a, jax.ShapeDtypeStruct) for a in args)


def test_accum_steps_matches_single_batch():
    """Gradient accumulation (into momentum) == one full-batch step."""
    cfg = smoke_variant(get_config("smollm-360m"))
    mesh = make_host_mesh()
    rules = AxisRules(mesh)
    params = M.init_params(cfg, jax.random.key(0))
    key = jax.random.key(1)
    B, S = 8, 32
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "weights": jnp.ones((B,))}
    outs = {}
    for A in (1, 4):
        tc = TrainConfig(learning_rate=1e-2, accum_steps=A, remat=False)
        step = steps.make_train_step(cfg, rules, tc)
        with set_mesh(mesh):
            p2, _, m = step(params, init_opt_state(params), batch)
        outs[A] = p2
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(outs[1]), jax.tree.leaves(outs[4]))]
    assert max(diffs) < 5e-3, max(diffs)


def test_lsgd_step_h1_matches_msgd():
    """shard_map lSGD with H=1 == the pjit mSGD train step (same math)."""
    cfg = smoke_variant(get_config("smollm-360m"))
    mesh = make_host_mesh()
    rules = AxisRules(mesh)
    params = M.init_params(cfg, jax.random.key(0))
    key = jax.random.key(1)
    B, S = 4, 32
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "weights": jnp.ones((B,))}
    tc = TrainConfig(learning_rate=1e-2, local_steps=1, remat=False)
    with set_mesh(mesh):
        msgd = steps.make_train_step(cfg, rules, tc)
        p_m, _, _ = msgd(params, init_opt_state(params), batch)
        lsgd = steps.make_lsgd_train_step(cfg, rules, tc)
        mom0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        p_l, _, _ = jax.jit(lsgd)(params, mom0, batch)
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(p_m), jax.tree.leaves(p_l))]
    assert max(diffs) < 5e-3, max(diffs)


def test_lsgd_step_h4_runs_and_learns():
    cfg = smoke_variant(get_config("qwen3-4b"))
    mesh = make_host_mesh()
    rules = AxisRules(mesh)
    params = M.init_params(cfg, jax.random.key(0))
    mom = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    key = jax.random.key(1)
    B, S = 8, 32  # 1 shard x H4 x L2
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "weights": jnp.ones((B,))}
    tc = TrainConfig(learning_rate=5e-3, local_steps=4, remat=False)
    step = jax.jit(steps.make_lsgd_train_step(cfg, rules, tc))
    with set_mesh(mesh):
        losses = []
        for _ in range(5):
            params, mom, m = step(params, mom, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_inference_2d_rules():
    mesh = make_host_mesh()
    r = AxisRules(mesh, inference_2d=True)
    assert r.batch is None  # activations replicated
    assert r.cache_batch is not None or len(jax.devices()) == 1
    r2 = AxisRules(mesh)
    assert (r2.batch is None) == (len(jax.devices()) == 1 and False) or True


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(env_dir, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched (JAX reads it itself);
    without it the cache sits at the checkout's fixed .jax_cache/."""
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = cache.init_compile_cache()
        if env_dir is None:
            want = cache.CHECKOUT_CACHE_DIR
            assert want.parent == pathlib.Path(__file__).resolve().parents[1]
            assert got == str(want) == jax.config.jax_compilation_cache_dir
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_scale_full_is_the_registered_config():
    from repro.launch.train import scale_config
    cfg = get_config("smollm-360m")
    assert scale_config(cfg, "full") is cfg
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.kv_heads(),
            cfg.d_ff, cfg.vocab_size, cfg.dtype) == (
                32, 960, 15, 5, 2560, 49152, "bfloat16")
    assert cfg.source == "hf:HuggingFaceTB/SmolLM-360M"
