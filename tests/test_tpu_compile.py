"""Compile the Pallas kernels (paged attention, the grouped expert matmul),
and the serving cells' paged decode steps, for a described TPU v5e.

Interpret mode never checks the TPU compiler's block-shape tiling rules, so
these tests compile the kernel (interpret=False) for a v5e chip that is
described, not attached: what the chip's compiler would refuse fails here.
Nothing runs; only the compiled program is inspected.
"""
import math
import os
import re

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

_MELLUM = get_config("mellum2-12b-a2.5b")

# (B, KV, G, hd, page_size, pages per sequence, Q, dtype); the pools are
# (L, N, page_size, KV*hd) at the model's depth, layer 1 read
_SHAPES = {
    "danube_q1": (8, *_HEADS, 16, 32, 1, jnp.bfloat16),
    "danube_q5": (8, *_HEADS, 16, 32, 5, jnp.bfloat16),
    # danube-serve-long's decode: 22 slots, a 256-page table
    "danube_serve_long": (22, *_HEADS, 16, 256, 1, jnp.bfloat16),
    # a chunked-prefill chunk of 256 tokens
    "danube_chunk256": (4, *_HEADS, 16, 256, 256, jnp.bfloat16),
    # mellum-serve-long's decode: 16 slots, 4 kv heads of 128
    "mellum_serve_long": (16, _MELLUM.kv_heads(),
                          _MELLUM.num_heads // _MELLUM.kv_heads(),
                          _MELLUM.head_dim_(), 16, 256, 1, jnp.bfloat16),
    "smoke": (3, 2, 4, 32, 8, 4, 1, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_paged_attention_compiles_for_v5e(name, one_chip,
                                          no_persistent_cache):
    B, KV, G, hd, ps, P, Q, dt = _SHAPES[name]
    L, N = 2 if name == "smoke" else _DANUBE.num_layers, B * P + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda q, k, v, t, n: paged_attention(
        q, k, v, 1, t, n, q_span=Q, interpret=False))
    pool = sds((L, N, ps, KV * hd), dt)
    compiled = fn.lower(sds((B, KV, Q * G, hd), dt), pool, pool,
                        sds((B, P), jnp.int32), sds((B,), jnp.int32)
                        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%paged_attention" in text

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


# (config, rows, pages a row, span Q, whether a prefill chunk) of each use
# of the paged step: the serving cells' decode programs, danube-serve-long
# (22 slots of 4,096 tokens) and mellum-serve-long (16 slots, the
# registered config's 16 held experts); danube's speculative verify of 4
# drafts; a 256-token chunk of a chunked prefill over 4 rows.  Pages of 16
# tokens
_DECODE_CELLS = {
    "danube_serve_long": (_DANUBE, 22, 256, 1, False),
    "danube_verify_q5": (_DANUBE, 22, 256, 5, False),
    "danube_chunk256": (_DANUBE, 4, 256, 256, True),
    "mellum_serve_long": (_MELLUM, 16, 256, 1, False),
}
_PAGE = 16
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "pred": 1, "s8": 1, "u8": 1}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                    r"(\{[^}]*\})? ([\w\-]+)\(")


def _pool_moves(text: str, n_pages: int, limit: int) -> list:
    """Instructions of any computation that copy, slice or update-slice an
    array of pages (a dim of `n_pages`) of `limit` bytes or more: a
    `copy`, `dynamic-slice` or `dynamic-update-slice`, or a fusion named
    for one."""
    moves = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dt, dims, _, op = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        kind = op if op != "fusion" else name
        if not re.search(r"copy|dynamic-slice|dynamic-update-slice", kind):
            continue
        if n_pages in shape and (
                _DTYPE_BYTES.get(dt, 4) * math.prod(shape) >= limit):
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("name", sorted(_DECODE_CELLS))
def test_paged_decode_keeps_the_pool_in_place_on_v5e(name, one_chip,
                                                     no_persistent_cache,
                                                     monkeypatch):
    """The engine's paged Pallas step (decode, verify or a prefill chunk)
    at a serving cell's shapes, compiled for a v5e: the K/V pools keep one
    unpadded row-major layout from entry to exit, both alias their donated
    inputs, nothing copies, slices or update-slices an array of pages of
    one layer's pool size or more, and the step's temporaries stay under
    1 GB."""
    import repro.kernels.ops as ops
    from repro.models import model as M

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg, B, P, Q, is_chunk = _DECODE_CELLS[name]
    N = B * P + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: sds(s.shape, s.dtype), M.param_sds(cfg))
    pools = jax.eval_shape(lambda: M.init_paged_cache(cfg, N, _PAGE))
    pools = {k: sds(v.shape, v.dtype) for k, v in pools["blocks"].items()}
    stats = cfg.family == "moe"

    def decode(params, blocks, meta):  # as `ServeEngine._build` packs it
        tok = meta[:B * Q].reshape(B, Q)
        pos = meta[B * Q:B * (Q + 1)]
        table = meta[B * (Q + 1):B * (Q + 1 + P)].reshape(B, P)
        lengths = meta[B * (Q + 1 + P):B * (Q + 2 + P)]
        cow = (meta[B * (Q + 2 + P):B * (Q + 3 + P)], meta[B * (Q + 3 + P):])
        logits, new, *moe = M.paged_step(
            cfg, params, {"blocks": blocks}, tok, pos, table, lengths,
            rules=None, impl="pallas", cow=cow, stats=stats and Q == 1)
        if Q > 1:  # a verify returns every position's argmax
            return jnp.argmax(logits, -1).astype(jnp.int32), new["blocks"]
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        return jnp.concatenate([nxt, *moe]), new["blocks"]

    def chunk(params, blocks, tokens, meta):  # as `ServeEngine._chunk_fn`
        offset, chunk_end = meta[:B], meta[B:2 * B]
        table = meta[2 * B:].reshape(B, P)
        logits, new = M.paged_step(
            cfg, params, {"blocks": blocks}, tokens, offset, table,
            chunk_end, rules=None, impl="pallas")
        at = jnp.clip(chunk_end - offset - 1, 0, Q - 1).astype(jnp.int32)
        last = jnp.take_along_axis(logits, at[:, None, None], axis=1)
        return jnp.argmax(last[:, -1], -1).astype(jnp.int32), new["blocks"]

    if is_chunk:
        compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
            params, pools, sds((B, Q), jnp.int32),
            sds((B * (2 + P),), jnp.int32)).compile()
    else:
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, pools, sds((B * (Q + 4 + P),), jnp.int32)).compile()
    text = compiled.as_text()
    pool = pools["k"]
    L, _, ps, lanes = pool.shape
    assert pool.shape == (cfg.num_layers, N, _PAGE,
                          cfg.kv_heads() * cfg.head_dim_())
    assert lanes % 128 == 0 and ps % 16 == 0  # whole bf16 (16, 128) tiles
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize

    # one layout, row-major and unpadded, at the entry and the exit
    layout = re.escape(f"bf16[{L},{N},{ps},{lanes}]") + r"\{([^}]*)\}"
    head = text.splitlines()[0]
    entry, _, exit_ = head.partition("entry_computation_layout=")[2] \
        .partition("->")
    assert re.findall(layout, entry) == re.findall(layout, exit_)
    assert len(re.findall(layout, entry)) == 2, head[:400]
    for lay in re.findall(layout, entry):
        assert lay.startswith("3,2,1,0"), lay

    moves = _pool_moves(text, N, pool_bytes // L)
    assert not moves, "\n".join(moves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes  # both pools donated
    assert mem.temp_size_in_bytes < 1e9, mem.temp_size_in_bytes
