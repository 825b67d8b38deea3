"""What several per-layer readers share. A reader file stays one small
``read(obs)`` of its own; quantities whose cells report different end-to-end
metrics are split into one file a kind, and call these."""
from chipbench.stats import percentile


def idle_pct(obs, kind):
    """Share of the traced window in which no operation ran on the device."""
    trace = obs["trace"]
    if obs["kind"] != kind or trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def compiles_in_window(obs, kind):
    """``cachedop.compile`` spans plus persistent-cache requests inside the
    window: nothing may compile there (reads 0)."""
    if obs["kind"] != kind:
        return None
    from mxnet_tpu import pcache
    spans = sum(1 for name, *_ in obs["spans"] if name == "cachedop.compile")
    requests = pcache.stats()["requests"] - obs["counters"]["pcache"]["requests"]
    return float(spans + requests)


def span_ms_p50(obs, name):
    """Median duration in ms of the program's spans of this name."""
    durs = [1e3 * (end - start) for n, start, end, _ in obs["spans"]
            if n == name]
    return percentile(durs, 50) if durs else None
