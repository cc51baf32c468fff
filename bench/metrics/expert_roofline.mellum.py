"""Roofline share of the grouped expert kernel (`expert_matmul`) in the
decode steps of the traced window: the least time the chip needs for the
expert work those steps had to do (each touched held expert's weights
read once, each routed row's SwiGLU computed; `work_mellum`), over the
kernel's device time inside the engine's decode program (`jit_decode`)."""
from bench import work_mellum
from bench.metrics._traced import kernel_time, traced_ticks

READS = ["device trace: expert_matmul custom calls in XLA Modules jit_decode",
         "TickRecord.moe_decode_rows_routed, moe_decode_experts_touched of "
         "the traced ticks"]


def read(data):
    red = data.get("trace")
    if not red:
        return None
    calls, secs = kernel_time(red, "expert_matmul", "jit_decode")
    ticks = traced_ticks(data)
    rows = sum(t["moe_decode_rows_routed"] for t in ticks)
    touched = sum(t["moe_decode_experts_touched"] for t in ticks)
    if not calls or secs <= 0 or not touched:
        return None
    flops, nbytes = work_mellum.expert_decode_work(data["model"], rows, touched)
    peak = data["peak"]
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / secs
