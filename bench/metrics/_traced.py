"""What the traced part of a serving window holds: the engine's tick
records between the `trace_start` and `trace_end` snapshots, and the
device time of one Pallas kernel, found by its own op name."""
from __future__ import annotations


def traced_ticks(data: dict) -> list:
    """Tick records of the ticks run while the profiler traced."""
    snaps, ticks = data.get("snaps") or {}, data.get("ticks") or []
    if "trace_start" not in snaps or "trace_end" not in snaps:
        return []
    base = snaps["open"]["tick"]
    return ticks[snaps["trace_start"]["tick"] - base:
                 snaps["trace_end"]["tick"] - base]


def kernel_time(red: dict, name: str, module: str) -> tuple:
    """(calls, device seconds) of the custom calls named `name` (`name` or
    `name.<n>` in the trace) inside program `module`.  Matching the whole
    op name, and not any op whose text holds it, leaves out the ops that
    read the kernel's output."""
    n, s = 0, 0.0
    for _, dur, oname, mname, _, _ in red["leaf_ops"]:
        if mname == module and oname.split(".")[0] == name:
            n += 1
            s += dur * 1e-9
    return n, s
