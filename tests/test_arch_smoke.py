"""Per-architecture smoke tests (deliverable f): reduced variant of each
family (2 layers, d_model<=512, <=4 experts) runs one forward/train step on
CPU; asserts output shapes + no NaNs.  Full configs are exercised only via
the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import set_mesh

from repro.configs import TrainConfig, get_config, list_archs, smoke_variant
from repro.launch.mesh import make_host_mesh
from repro.launch import steps
from repro.models import model as M
from repro.optim import init_opt_state
from repro.sharding import AxisRules

ARCHS = [a for a in list_archs() if not a.startswith("chicle")]
B, S = 2, 64


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
        "weights": jnp.ones((B,), jnp.float32),
    }
    if cfg.family in ("audio", "vlm"):
        T = cfg.encoder_seq or cfg.num_image_tokens
        batch["memory"] = jax.random.normal(ks[2], (B, T, cfg.d_model),
                                            jnp.float32) * 0.02
    return batch


@pytest.fixture(scope="module")
def mesh_rules():
    mesh = make_host_mesh()
    return mesh, AxisRules(mesh)


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_variant_is_reduced(arch):
    cfg = smoke_variant(get_config(arch))
    assert cfg.num_layers <= 2 or (cfg.family in ("hybrid", "vlm"))
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, mesh_rules):
    mesh, rules = mesh_rules
    cfg = smoke_variant(get_config(arch))
    params = M.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    with set_mesh(mesh):
        logits, aux = M.forward(cfg, params, batch["tokens"],
                                memory=batch.get("memory"), rules=rules)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch, mesh_rules):
    mesh, rules = mesh_rules
    cfg = smoke_variant(get_config(arch))
    tc = TrainConfig(learning_rate=1e-3, remat=False)
    params = M.init_params(cfg, jax.random.key(0))
    opt_state = init_opt_state(params)
    batch = _batch(cfg, jax.random.key(1))
    step = steps.make_train_step(cfg, rules, tc)
    with set_mesh(mesh):
        new_params, new_opt, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a - b))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch, mesh_rules):
    mesh, rules = mesh_rules
    cfg = smoke_variant(get_config(arch))
    params = M.init_params(cfg, jax.random.key(0))
    mem_len = cfg.encoder_seq or cfg.num_image_tokens
    cache = M.init_cache(cfg, B, 32, cross_len=mem_len)
    with set_mesh(mesh):
        logits, cache2 = M.decode_step(
            cfg, params, cache, jnp.zeros((B, 1), jnp.int32), jnp.int32(0),
            rules=rules)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ["smollm-360m", "rwkv6-1.6b",
                                  "jamba-1.5-large-398b"])
def test_prefill_matches_decode(arch, mesh_rules):
    """Prefill-then-decode == forward over the same tokens (last logits)."""
    mesh, rules = mesh_rules
    cfg = smoke_variant(get_config(arch))
    params = M.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (B, 16), 0, cfg.vocab_size)
    with set_mesh(mesh):
        full_logits, _ = M.forward(cfg, params, toks, rules=rules, remat=False)
        pre_logits, cache = M.prefill(cfg, params, toks[:, :-1], rules=rules,
                                      remat=False, cache_len=32)
        dec_logits, _ = M.decode_step(cfg, params, cache, toks[:, -1:],
                                      jnp.int32(15), rules=rules)
    # tolerance: chunked-scan prefill vs stepwise decode accumulate fp32
    # differently (SSM decay cumsums); logits agree to ~1e-1 absolute.
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-2, atol=1e-1)
