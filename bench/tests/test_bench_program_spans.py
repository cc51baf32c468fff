"""What the benchmark reads from the program besides its results: the
program names its device-trace readers find modules by, and
`prefill_wait_share.long`, from the ticks' time blocked on prefills."""
from __future__ import annotations

import json
import os
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import common, run  # noqa: E402
from test_bench_harness import BIG_SEED, tiny_cell  # noqa: E402


def _module(lowered) -> str:
    """`module @jit_decode attributes ...` -> `jit_decode`."""
    return lowered.as_text().split("@", 1)[1].split()[0]


def test_program_names_that_readers_find_modules_by():
    """`paged_attn_roofline.long` finds the kernel inside `jit_decode`, and
    a trace's programs are told apart by these names: a rename has to fail
    here, not null a metric."""
    import jax
    import jax.numpy as jnp
    from bench import weights
    from repro.configs import TrainConfig
    from repro.launch.elastic import ElasticTrainer
    from repro.serve import ServeEngine

    cell = tiny_cell("danube-serve-long")
    m = cell["model"]
    cfg = common.program_config(m)
    eng = ServeEngine(cfg, params=weights.for_program(m, 1, cfg), seed=0,
                      **cell["engine"])
    cap, i32, bucket = eng.capacity, np.int32, eng.prefill_bucket
    _, _, decode_fn, _ = eng._k_cache[eng._k_mesh(eng.k)]
    meta = eng._pack_meta(np.zeros((cap, 1), i32), np.zeros(cap, i32),
                          np.full((cap, 1), -1, i32), np.zeros(cap, i32),
                          np.zeros(cap, i32), np.zeros(cap, i32))
    prefill = eng._prefill_fn(bucket)
    args = (eng.params, jnp.zeros((1, bucket), i32),
            eng._pack_meta(np.full(1, bucket, i32)))
    _, rk, rv = jax.eval_shape(prefill, *args)
    ids = eng._pack_meta(np.zeros(bucket // eng.page_size, i32))
    with jax.set_mesh(eng.mesh):
        assert _module(decode_fn.lower(eng.params, eng.blocks, meta)) == "jit_decode"
        assert _module(prefill.lower(*args)) == "jit_prefill"
        assert _module(eng._insert_fn(1, bucket).lower(eng.blocks, rk, rv, ids)) \
            == "jit_insert"

    train = tiny_cell("smollm-train")
    tcfg = common.program_config(train["model"])
    tr = ElasticTrainer(tcfg, TrainConfig(optimizer="sgdm", remat=False))
    S = train["mix"]["seq_len"]
    batch = {"tokens": jnp.zeros((2, S), i32), "labels": jnp.zeros((2, S), i32),
             "weights": jnp.ones((2,), jnp.float32)}
    with jax.set_mesh(tr.mesh):
        assert _module(tr.step.lower(tr.params, tr.opt_state, batch)) \
            == "jit_train_step"


def test_prefill_wait_share_reads_the_window_ticks():
    reader = run.load_reader("prefill_wait_share.long")
    snaps = {"open": {"t": 10.0}, "close": {"t": 30.0}}
    ticks = [{"prefill_wait_s": w} for w in (0.0, 0.25, 0.0, 0.75)]
    assert reader.read({"ticks": ticks, "snaps": snaps}) == pytest.approx(5.0)
    # a program whose ticks do not record the wait: nothing to read
    assert reader.read({"ticks": [{"tokens_emitted": 3}], "snaps": snaps}) is None


def test_traced_serve_run_prints_prefill_wait_share(capsys):
    rc, out, _ = run.execute("danube-serve-long", BIG_SEED, 2.0, 1,
                             cell=tiny_cell("danube-serve-long"),
                             require_tpu=False)
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == out
    value = out["metrics"]["prefill_wait_share.long"]
    assert value["unit"] == "%" and 0.0 < value["value"] <= 100.0
    assert "decode_occupancy.long" in out["metrics"]
