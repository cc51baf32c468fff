"""Share of the serving window in which the host waited on prefills: the
time the window's ticks blocked in `prefill.device_wait`
(`TickRecord.prefill_wait_s`), over the window.  With overlapped ticks the
decode step has settled before that wait, so every decoding slot waits in
it too."""
READS = ["TickRecord.prefill_wait_s", "host clock at the window's open and close"]


def read(data):
    ticks, snaps = data.get("ticks"), data.get("snaps")
    if not ticks or not snaps or "prefill_wait_s" not in ticks[0]:
        return None
    window_s = snaps["close"]["t"] - snaps["open"]["t"]
    if window_s <= 0:
        return None
    return 100.0 * sum(t["prefill_wait_s"] for t in ticks) / window_s
