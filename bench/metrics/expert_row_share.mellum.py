"""Share of the rows the grouped expert kernel computes that were routed
to a held expert, over the window's decode and whole-prompt prefill
dispatches: the rest is tile padding (a row tile that a group fills only
in part is computed whole)."""
READS = ["TickRecord.moe_rows_routed, moe_rows_computed"]


def read(data):
    ticks = data.get("ticks") or []
    computed = sum(t.get("moe_rows_computed", 0) for t in ticks)
    if not computed:
        return None
    return 100.0 * sum(t["moe_rows_routed"] for t in ticks) / computed
