"""Find, once, the highest rate an open-loop cell's system sustains: one
process, one build, the cell's own lengths at each of a few fixed rates.
The cell's ``rate_per_s`` is then written as a number (about four fifths of
the knee); the benchmark's own runs never search.

    python -m chipbench.sweep --workload <open-loop cell> --rates 4,6,8,10,12 --seconds 20
"""
import argparse
import json
import sys
import time

from chipbench import run, serving
from chipbench.stats import percentile
from chipbench.traffic import open_loop


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2147483777)
    args = ap.parse_args()
    _entry, cfg, cell = run.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("sweep reads the chip: jax came up on %s" % devices)
    ctx = run.Context(cfg, cell, args.seed, args.seconds, False, devices[:1])
    builder, system = serving.build_and_warm(ctx)
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        at = run.Context(cfg, dict(cell, rate_per_s=rate), args.seed + n,
                         args.seconds, False, devices[:1])
        records, t0 = open_loop.offer(at, system)
        open_at_close = sum(1 for r in records if not r.done.is_set())
        out = serving.finish(at, builder, system, records, t0)
        drain_s = time.monotonic() - (t0 + args.seconds)
        waits = [1e3 * (r["admitted_t"] - r["enqueue_t"])
                 for r in out["observations"]["requests"]
                 if r["admitted_t"] is not None]
        asked = sum(r.new_tokens for r in records) / args.seconds
        print(json.dumps({
            "rate_per_s": rate, "requests": len(records),
            "failed": out["failed"], "offered_tokens_per_s": asked,
            "open_at_close": open_at_close, "drain_s": drain_s,
            "queue_wait_ms_p50": percentile(waits, 50),
            "queue_wait_ms_p95": percentile(waits, 95),
            "memory_peak_bytes": (devices[0].memory_stats() or {}).get(
                "peak_bytes_in_use"),
            **out["end_to_end"]}), flush=True)
    system.close()


if __name__ == "__main__":
    main()
