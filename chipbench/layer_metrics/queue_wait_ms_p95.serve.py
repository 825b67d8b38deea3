"""95th percentile of the time the window's requests waited for a slot:
the program's own ``admitted_t - enqueue_t``."""
from chipbench.stats import percentile


def read(obs):
    if obs["kind"] != "serve":
        return None
    waits = [1e3 * (r["admitted_t"] - r["enqueue_t"]) for r in obs["requests"]
             if r["admitted_t"] is not None and r["enqueue_t"] is not None]
    return percentile(waits, 95) if waits else None
