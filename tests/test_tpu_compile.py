"""Compile the Pallas kernels (paged attention, the grouped expert matmul)
for a described TPU v5e.

Interpret mode never checks the TPU compiler's block-shape tiling rules, so
these tests compile the kernel (interpret=False) for a v5e chip that is
described, not attached: what the chip's compiler would refuse fails here.
Nothing runs; only the compiled program is inspected.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention

_DANUBE = get_config("h2o-danube-1.8b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_HEADS = (_DANUBE.kv_heads(), _DANUBE.num_heads // _DANUBE.kv_heads(),
          _DANUBE.head_dim_())

# (B, KV, G, hd, page_size, pages per sequence, Q, dtype)
_SHAPES = {
    "danube_q1": (8, *_HEADS, 16, 32, 1, jnp.bfloat16),
    "danube_q5": (8, *_HEADS, 16, 32, 5, jnp.bfloat16),
    # danube-serve-long's decode: 22 slots, a 256-page table
    "danube_serve_long": (22, *_HEADS, 16, 256, 1, jnp.bfloat16),
    # a chunked-prefill chunk of 256 tokens
    "danube_chunk256": (4, *_HEADS, 16, 256, 256, jnp.bfloat16),
    "smoke": (3, 2, 4, 32, 8, 4, 1, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_paged_attention_compiles_for_v5e(name, one_chip,
                                          no_persistent_cache):
    B, KV, G, hd, ps, P, Q, dt = _SHAPES[name]
    N = B * P + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda q, k, v, t, n: paged_attention(
        q, k, v, t, n, q_span=Q, interpret=False))
    compiled = fn.lower(sds((B, KV, Q * G, hd), dt),
                        sds((N, ps, KV, hd), dt), sds((N, ps, KV, hd), dt),
                        sds((B, P), jnp.int32), sds((B,), jnp.int32)
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


_MELLUM = get_config("mellum2-12b-a2.5b")

# (rows, k, n): mellum-serve-long's decode step (16 slots x top-8) and a
# 3,584-token prefill, through gate/up (2304 -> 896) and down (896 -> 2304)
_EXPERT_SHAPES = {
    "mellum_decode_gate": (16 * 8, _MELLUM.d_model, _MELLUM.d_ff),
    "mellum_decode_down": (16 * 8, _MELLUM.d_ff, _MELLUM.d_model),
    "mellum_prefill_gate": (3584 * 8, _MELLUM.d_model, _MELLUM.d_ff),
    "mellum_prefill_down": (3584 * 8, _MELLUM.d_ff, _MELLUM.d_model),
}


@pytest.mark.parametrize("name", sorted(_EXPERT_SHAPES))
def test_expert_matmul_compiles_for_v5e(name, one_chip, no_persistent_cache):
    from repro.kernels.grouped_matmul import expert_matmul

    m, k, n = _EXPERT_SHAPES[name]
    L, (_, G) = _MELLUM.num_layers, _MELLUM.held_experts()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda r, w, s, l: expert_matmul(r, w, s, l,
                                                  interpret=False))
    compiled = fn.lower(sds((m, k), jnp.bfloat16),
                        sds((L, G, k, n), jnp.bfloat16),
                        sds((G + 1,), jnp.int32), sds((), jnp.int32)
                        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%expert_matmul" in text
