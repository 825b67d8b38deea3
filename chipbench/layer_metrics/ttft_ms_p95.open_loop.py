"""95th percentile of (first token received - time due) over the window's
requests of an open-loop cell, a failed request counting as the worst: the
end-to-end ``ttft_ms_p95`` of the closed-loop cells, kept per-layer where
some 135 requests a window make it too unsteady to carry a bound (its runs
spread by 7-10%, PERF.md section 2)."""
from chipbench.stats import percentile


def read(obs):
    if obs["kind"] != "serve" or obs["cell"]["kind"] != "open_loop":
        return None
    worst = 1e3 * (max(t for r in obs["requests"] for t in r["token_times"])
                   - obs["t0"])
    waits = [1e3 * (r["token_times"][0] - r["due"]) if r["ok"] else worst
             for r in obs["requests"]]
    return percentile(waits, 95) if waits else None
