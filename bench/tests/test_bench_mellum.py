"""The Mellum serving cell on the CPU at a tiny size, through
`run.execute`: the program's run is correct and its float8 control arm is
not; and the cell's own metric readers on records of known content."""
from __future__ import annotations

import json
import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import common, run, work_mellum  # noqa: E402

BIG_SEED = 2**31 + 4242
CELL = "mellum-serve-long"


def tiny_model(m: dict) -> dict:
    """Mellum's file at a size the CPU serves in seconds: one whole period
    of layers, narrow widths, 8 experts of which 4 are held, top-2, a
    16-token window; weights six times the assumed spread (0.02), without
    which a few narrow layers only copy the last token and no precision
    changes what is served (at this seed: program 0.0065, float8 0.45)."""
    L = 4
    m.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, moe_intermediate_size=64, num_hidden_layers=L,
             layer_types=m["layer_types"][:L],
             mlp_layer_types=m["mlp_layer_types"][:L], vocab_size=4096,
             num_experts=4, num_experts_per_tok=2, sliding_window=16,
             initializer_range=0.12, published={"num_experts": 8})
    return m


def tiny_cell() -> dict:
    cell = common.load_cell(CELL)
    tiny_model(cell["model"])
    cell["engine"].update(capacity=4, cache_len=256, prefill_bucket=64)
    mix = cell["mix"]
    mix["prompt_tokens"].update(median=48, min=16, max=120)
    mix["output_tokens"].update(median=16, min=4, max=40)
    mix.update(rate_per_s=3.0, lead_s=1.0)
    return cell


def test_tiny_run_is_correct_and_control_fails(capsys):
    rc, out, arms = run.execute(CELL, BIG_SEED, 2.0, 0, cell=tiny_cell(),
                                require_tpu=False, control=True)
    assert rc == 0
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(arms) == {"control_fp8"}
    assert arms["control_fp8"]["correct"] is False, arms
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert lines[-1]["correct"] is True


def _tick(**kw):
    t = {"tokens_emitted": 0, "moe_rows_routed": 0, "moe_rows_computed": 0,
         "moe_decode_rows_routed": 0, "moe_decode_experts_touched": 0,
         "attn_keys_window": 0, "attn_keys_full": 0}
    t.update(kw)
    return t


def _data(ticks, ops):
    """Records of a traced window: ticks 10..13 of a window opened at tick
    10, the profiler on for ticks 11 and 12; `ops` as the trace reduction
    gives leaf operations (start, ns, op name, module, text, device)."""
    m = common.load_json(ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
    return {"ticks": ticks, "model": m,
            "snaps": {"open": {"tick": 10}, "trace_start": {"tick": 11},
                      "trace_end": {"tick": 13}},
            "trace": {"leaf_ops": ops},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_read_the_traced_ticks_and_their_own_kernel():
    ticks = [_tick(moe_decode_rows_routed=99, moe_decode_experts_touched=99),
             _tick(tokens_emitted=4, moe_rows_routed=8, moe_rows_computed=32,
                   moe_decode_rows_routed=8, moe_decode_experts_touched=10,
                   attn_keys_window=3000, attn_keys_full=1000),
             _tick(tokens_emitted=4, moe_rows_routed=8, moe_rows_computed=32,
                   moe_decode_rows_routed=8, moe_decode_experts_touched=6,
                   attn_keys_window=3000, attn_keys_full=1000),
             _tick(moe_rows_routed=16, moe_rows_computed=64)]
    ops = [(0, 1000, "expert_matmul.3", "jit_decode", "", 0),
           (0, 1000, "expert_matmul", "jit_decode", "", 0),
           (0, 5000, "expert_matmul.4", "jit_prefill", "", 0),
           (0, 500, "paged_attention.15", "jit_decode", "", 0),
           # reads the kernel's output: not the kernel
           (0, 7000, "fusion.2", "jit_decode", "fusion(%paged_attention.15)", 0)]
    data = _data(ticks, ops)
    m = data["model"]
    flops, nbytes = work_mellum.expert_decode_work(m, 16, 16)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 2e-6
    assert run.load_reader("expert_roofline.mellum").read(data) == pytest.approx(want)
    flops, nbytes = work_mellum.attn_decode_work(m, 8000, 8)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 0.5e-6
    assert run.load_reader("paged_attn_roofline.mellum").read(data) == pytest.approx(want)
    # every tick of the window, prefill rows included
    assert run.load_reader("expert_row_share.mellum").read(data) == 25.0


def test_work_counts_by_hand():
    m = common.load_json(ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json")
    # a touched expert: gate, up and down, 2304 x 896 each, bfloat16
    assert work_mellum.expert_decode_work(m, 1, 1) == (
        6 * 2304 * 896, 3 * 2304 * 896 * 2)
    # 32 query heads of 128 over a key; K and V of 4 KV heads; q and the
    # output once a token on each of 28 layers
    assert work_mellum.attn_decode_work(m, 1, 0) == (4 * 32 * 128,
                                                     2 * 4 * 128 * 2)
    assert work_mellum.attn_decode_work(m, 0, 1)[1] == 2 * 28 * 32 * 128 * 2
