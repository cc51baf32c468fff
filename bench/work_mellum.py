"""Operations and bytes of the decode work that Mellum's kernels must do,
from the published widths (`bench/configs/mellum2-12b-a2.5b.json`) and the
engine's per-tick counters.  Matmul FLOPs count 2 per multiply-add;
weights and the KV cache are bfloat16 as served.
"""
from __future__ import annotations

BF16 = 2  # bytes of one element as served


def expert_decode_work(m: dict, rows_routed: int, experts_touched: int) -> tuple:
    """(FLOPs, bytes) of the grouped expert kernel over decode steps in
    which `rows_routed` (token, expert) rows reached held experts and
    `experts_touched` held experts (summed over layers and steps) had at
    least one: each row's SwiGLU expert (gate, up and down projections),
    and each touched expert's three weight matrices read once."""
    D, F = m["hidden_size"], m["moe_intermediate_size"]
    return (6.0 * D * F * rows_routed,
            3.0 * D * F * BF16 * experts_touched)


def attn_decode_work(m: dict, keys: int, tokens: int) -> tuple:
    """(FLOPs, bytes) of the paged-attention kernel over decode steps whose
    queries must attend `keys` keys in all (summed over layers: at most
    the window on a sliding layer, every live key on a full one) for
    `tokens` new tokens: QK^T and PV per key and query head; each key's K
    and V rows read once per KV head, and q read and the output written
    once a token per layer."""
    L = m["num_hidden_layers"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    flops = 4.0 * H * hd * keys
    nbytes = 2.0 * KV * hd * BF16 * keys + 2.0 * L * H * hd * BF16 * tokens
    return flops, nbytes
