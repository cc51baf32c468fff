"""Roofline share of the Pallas paged-attention kernel (`paged_attention`)
in the decode steps of the traced window: the least time the chip needs
for the keys those steps' queries had to attend (at most the window on a
sliding layer, every live key on a full one; `work_mellum`), over the
kernel's device time inside the engine's decode program (`jit_decode`).
The kernel is found by its own op name: the grouped expert kernel is a
`tpu_custom_call` of the same program."""
from bench import work_mellum
from bench.metrics._traced import kernel_time, traced_ticks

READS = ["device trace: paged_attention custom calls in XLA Modules jit_decode",
         "TickRecord.attn_keys_window, attn_keys_full, tokens_emitted of the "
         "traced ticks"]


def read(data):
    red = data.get("trace")
    if not red:
        return None
    calls, secs = kernel_time(red, "paged_attention", "jit_decode")
    ticks = traced_ticks(data)
    keys = sum(t["attn_keys_window"] + t["attn_keys_full"] for t in ticks)
    tokens = sum(t["tokens_emitted"] for t in ticks)
    if not calls or secs <= 0 or not keys:
        return None
    flops, nbytes = work_mellum.attn_decode_work(data["model"], keys, tokens)
    peak = data["peak"]
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / secs
