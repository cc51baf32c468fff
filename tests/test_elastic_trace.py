"""The elastic trainer's spans: `train_step` runs under `train.put`,
`train.dispatch` and `train.sync`, and a traced trainer trains exactly as
an untraced one."""
import jax
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config, smoke_variant
from repro.data import make_lm_tokens
from repro.launch.elastic import ElasticTrainer
from repro.obs import Tracer

B, S = 4, 32


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("smollm-360m"))
    tc = TrainConfig(learning_rate=5e-3, remat=False)
    data = make_lm_tokens(64, S, cfg.vocab_size, seed=0)

    def batch(i):
        sl = slice(B * i, B * (i + 1))
        return {"tokens": np.asarray(data["tokens"][sl]),
                "labels": np.asarray(data["labels"][sl]),
                "weights": np.ones((B,), np.float32)}

    return cfg, tc, batch


def test_train_step_spans_in_order(setup):
    cfg, tc, batch = setup
    trc = Tracer()
    tr = ElasticTrainer(cfg, tc, tracer=trc)
    tr.train_step(batch(0))
    spans = trc.spans(track="train")
    assert [e.name for e in spans] == [
        "train.put", "train.dispatch", "train.sync"]
    put, dispatch, sync = spans
    assert put.ts + put.dur <= dispatch.ts
    assert dispatch.ts + dispatch.dur <= sync.ts
    assert sync.cat == "device" and put.cat == dispatch.cat == "host"


@pytest.mark.parametrize("profiler", [False, True],
                         ids=["tracer", "tracer+profiler"])
def test_traced_trainer_is_bit_identical(setup, profiler):
    cfg, tc, batch = setup
    plain = ElasticTrainer(cfg, tc)
    traced = ElasticTrainer(cfg, tc, tracer=Tracer(profiler=profiler))
    for i in range(2):
        assert plain.train_step(batch(i)) == traced.train_step(batch(i))
    for a, b in zip(jax.tree.leaves((plain.params, plain.opt_state)),
                    jax.tree.leaves((traced.params, traced.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
