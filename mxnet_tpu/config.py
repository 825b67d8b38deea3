"""Environment-variable configuration system.

Parity surface: the reference's ~58 documented ``MXNET_*`` knobs
(reference ``docs/static_site/src/pages/api/faq/env_var.md``). Every
documented name is registered here with its reference default and its
disposition on this TPU stack:

- ``wired``      — read and honored by a subsystem in this codebase
- ``subsumed``   — the concern is owned by XLA/PJRT (schedulers, memory
                   pools, kernel autotuning, fusion): setting it is
                   accepted and recorded but has no separate effect,
                   because there is no hand-rolled engine to tune
- ``n/a``        — CUDA/MKLDNN/Cython specifics with no TPU counterpart

Use :func:`get` for typed reads, :func:`describe` for the full table
(the runtime analogue of the reference doc page).
"""
from __future__ import annotations

import os

__all__ = ["get", "set", "describe", "KNOBS"]


class Knob:
    __slots__ = ("name", "default", "typ", "disposition", "doc")

    def __init__(self, name, default, typ, disposition, doc):
        self.name = name
        self.default = default
        self.typ = typ
        self.disposition = disposition
        self.doc = doc


def _k(name, default, typ, disp, doc):
    return name, Knob(name, default, typ, disp, doc)


KNOBS = dict([
    # ---- wired ------------------------------------------------------------
    _k("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice", str, "wired",
       "NaiveEngine = blocking dispatch for debugging (engine.py)"),
    _k("MXNET_CPU_WORKER_NTHREADS", 1, int, "wired",
       "host-side worker threads: DataLoader default num_workers and the "
       "native IO pump decode pool"),
    _k("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", 15, int, "wired",
       "bulk-dispatch span size hint (engine.py bulk context)"),
    _k("MXNET_PROFILER_AUTOSTART", 0, int, "wired",
       "start the profiler at import (profiler.py)"),
    _k("MXNET_CACHED_OP_CAPACITY", 64, int, "wired",
       "max compiled signatures retained per CachedOp (LRU; <=0 means "
       "unbounded) — bounds XLA executable memory under shape churn"),
    _k("MXNET_PROFILER_MODE", 0, int, "wired",
       "profile symbolic-only (0) or all (1) operators"),
    _k("MXNET_UPDATE_ON_KVSTORE", 0, int, "wired",
       "run the optimizer inside the kvstore (model._create_kvstore)"),
    _k("MXNET_GLUON_REPO", "https://apache-mxnet.s3-accelerate."
       "dualstack.amazonaws.com/", str, "wired",
       "base URL for model-zoo/dataset downloads (no egress here: used "
       "only to compute cache paths)"),
    _k("MXNET_HOME", os.path.join(os.path.expanduser("~"), ".mxnet"), str,
       "wired", "cache directory for datasets and model parameters"),
    _k("MXNET_ENFORCE_DETERMINISM", 0, int, "wired",
       "XLA on TPU is deterministic given fixed seeds; flag recorded and "
       "surfaced via runtime features"),
    _k("MXNET_SAFE_ACCUMULATION", 0, int, "wired",
       "bf16 matmuls already accumulate in fp32 on the MXU; reductions "
       "here run in fp32 — flag accepted for script parity"),
    _k("MXNET_CHAOS_SPEC", "", str, "wired",
       "fault-injection spec armed at import (resilience/chaos.py): "
       "'point:kind[:trigger];...' e.g. serving.execute:transient:first=2"),
    _k("MXNET_RETRY_MAX_ATTEMPTS", 3, int, "wired",
       "default RetryPolicy total attempts (resilience/retry.py)"),
    _k("MXNET_RETRY_BASE_DELAY_MS", 10.0, float, "wired",
       "default RetryPolicy first backoff delay"),
    _k("MXNET_RETRY_MAX_DELAY_MS", 1000.0, float, "wired",
       "default RetryPolicy backoff cap"),
    _k("MXNET_RETRY_DEADLINE_MS", 0.0, float, "wired",
       "default RetryPolicy wall-clock budget across attempts (0 = none)"),
    _k("MXNET_BREAKER_FAILURE_THRESHOLD", 5, int, "wired",
       "serving circuit breaker: consecutive failures before opening "
       "(resilience/breaker.py; <=0 disables the ModelServer breaker)"),
    _k("MXNET_BREAKER_RECOVERY_MS", 1000.0, float, "wired",
       "serving circuit breaker: open-state hold before half-open probes"),
    _k("MXNET_BREAKER_HALF_OPEN_PROBES", 1, int, "wired",
       "serving circuit breaker: successful probes required to close"),
    _k("MXNET_RESUME_EVERY", 10, int, "wired",
       "resumable_fit checkpoint cadence in steps (resilience/resume.py)"),
    _k("MXNET_GUARDRAILS_CLIP_NORM", 0.0, float, "wired",
       "GuardedStep global-norm gradient clip fused into the step "
       "(resilience/guardrails.py; 0 = off)"),
    _k("MXNET_GUARDRAILS_DYNAMIC_SCALE", 0, int, "wired",
       "GuardedStep dynamic loss scaling as traced state (grow/halve; "
       "needed for true fp16, off for bf16/f32)"),
    _k("MXNET_GUARDRAILS_INIT_SCALE", 2.0 ** 16, float, "wired",
       "initial loss scale when dynamic scaling is on (reference AMP "
       "LossScaler default)"),
    _k("MXNET_GUARDRAILS_SCALE_FACTOR", 2.0, float, "wired",
       "loss-scale grow/halve factor (power of 2 keeps fp32 exact)"),
    _k("MXNET_GUARDRAILS_SCALE_WINDOW", 2000, int, "wired",
       "consecutive clean steps before the loss scale grows"),
    _k("MXNET_GUARDRAILS_DEADLINE_MS", 0.0, float, "wired",
       "GuardedStep watchdog: flag steps whose results are not ready "
       "within this many ms (0 = no watchdog)"),
    _k("MXNET_GUARDRAILS_STORM_WINDOW", 20, int, "wired",
       "AnomalyDetector NaN-storm window (recent steps considered)"),
    _k("MXNET_GUARDRAILS_STORM_SKIPS", 5, int, "wired",
       "skipped steps within the storm window that declare a NaN storm "
       "(raises AnomalyFault -> resumable_fit restore-and-replay)"),
    _k("MXNET_DATALOADER_MAX_SKIPS", 100, int, "wired",
       "DataLoader error_policy='skip': bad samples tolerated per "
       "iteration before failing loudly (<0 = unbounded)"),
    _k("MXNET_DATAFEED_DEPTH", 4, int, "wired",
       "DeviceFeed staging ring depth: batches dispatched to sharded "
       "device buffers ahead of consumption (parallel/datafeed.py)"),
    _k("MXNET_DATAFEED_CHUNK", 8, int, "wired",
       "ShardedTrainer.step_stream steps per compiled lax.scan span — "
       "chunk N+1 stages while chunk N computes"),
    _k("MXNET_ELASTIC_HEARTBEAT_MS", 1000.0, float, "wired",
       "ElasticMember background-beater cadence (resilience/elastic.py); "
       "per-step beats fire regardless"),
    _k("MXNET_ELASTIC_DEADLINE_MS", 15000.0, float, "wired",
       "missed-beat deadline after which the coordinator/supervisor "
       "declares a host dead (covers compile gaps; lower it on fast "
       "steps for quicker failover)"),
    _k("MXNET_ELASTIC_GRACE_MS", 10000.0, float, "wired",
       "SIGTERM->eviction grace window: the emergency checkpoint must "
       "publish within this budget (PreemptionHandler)"),
    _k("MXNET_ELASTIC_MAX_RESTARTS", 2, int, "wired",
       "launch.py --supervise: consecutive crash-restarts per worker "
       "before it is evicted and the mesh re-forms at world-1"),
    _k("MXNET_ELASTIC_BACKOFF_MS", 500.0, float, "wired",
       "launch.py --supervise: first restart backoff (doubles per "
       "consecutive failure of the same worker)"),
    _k("MXNET_ELASTIC_MIN_WORLD", 1, int, "wired",
       "launch.py --supervise: smallest world size worth re-forming to; "
       "below it the run fails instead of limping"),
    _k("MXNET_ELASTIC_COLLECTIVE_DEADLINE_MS", 0.0, float, "wired",
       "collective watchdog: abort a kvstore allreduce/barrier that is "
       "still blocked after this many ms (hung-peer wedge -> "
       "CollectiveTimeout; 0 = off)"),
    _k("MXNET_GEN_SLOTS", 8, int, "wired",
       "generation serving: KV-cache arena slots == max sequences decoded "
       "per fused step (serving/generation/kvcache.py)"),
    _k("MXNET_GEN_MAX_SEQ", 256, int, "wired",
       "generation serving: per-slot KV capacity (prompt + generated), "
       "capped to the model's max_len"),
    _k("MXNET_GEN_LADDER", "16,32,64,128", str, "wired",
       "generation serving: prefill bucket ladder (comma-separated rungs; "
       "prompts pad up to a rung, compiles bounded by the ladder)"),
    _k("MXNET_GEN_MAX_NEW_TOKENS", 128, int, "wired",
       "generation serving: default per-request token budget"),
    _k("MXNET_GEN_TOP_K", 0, int, "wired",
       "generation serving: static top-k sampling filter baked into the "
       "decode program (0 = off; per-request temperature stays dynamic)"),
    _k("MXNET_GEN_QUEUE_SIZE", 64, int, "wired",
       "generation serving: waiting-request bound before ServerBusy "
       "backpressure (serving/generation/scheduler.py)"),
    _k("MXNET_GEN_PREFILL_CHUNK", 0, int, "wired",
       "generation serving: chunked-prefill rung size — long prompts are "
       "split into chunks of this many tokens interleaved with decode "
       "iterations, so a 4k prompt no longer stalls every live stream's "
       "next token (0 = monolithic prefill; 128 is a good chip default)"),
    _k("MXNET_GEN_PREFIX_CACHE", 1, int, "wired",
       "generation serving: copy-on-admit prefix KV cache — admits whose "
       "prompt starts with a cached prefix copy the slab into their slot "
       "via dynamic_update_slice and skip that many prefill tokens "
       "(serving/generation/prefix_cache.py; 0 = off)"),
    _k("MXNET_GEN_PREFIX_BLOCK", 32, int, "wired",
       "prefix cache sharing granularity: prefixes are stored/probed at "
       "multiples of this many tokens — finer blocks skip more of a "
       "shared prompt, coarser blocks bound entry count"),
    _k("MXNET_GEN_PREFIX_CACHE_MB", 256, int, "wired",
       "prefix cache slab-byte budget; exceeding it LRU-evicts entries "
       "whose refcount is zero (<= 0 disables the bound)"),
    _k("MXNET_GEN_SPEC_K", 4, int, "wired",
       "speculative decoding: draft tokens proposed per verify step "
       "(serving/generation/speculative.py; the scheduler engages the "
       "speculative path only when a draft engine is attached)"),
    _k("MXNET_GEN_LANE", "mixed", str, "wired",
       "generation lane policy: 'mixed' (default), 'prefill' (requests "
       "retire after first token + prefix-cache publish — the "
       "disaggregation handoff), or 'decode' (admits expect prefix-cache "
       "coverage; misses are counted as decode_lane_misses)"),
    _k("MXNET_HTTP_MAX_BODY", 8 * 1024 * 1024, int, "wired",
       "ModelServer POST body cap in bytes: a larger client-declared "
       "Content-Length is consumed in bounded chunks and refused with "
       "413 (keep-alive stays in sync); <= 0 disables the cap"),
    _k("MXNET_FLEET_CANARY_FRACTION", 0.1, float, "wired",
       "fleet serving: default share of a model's traffic routed to its "
       "canary version (deterministic by request-id hash; "
       "serving/fleet.py)"),
    _k("MXNET_FLEET_CANARY_MIN_SAMPLES", 20, int, "wired",
       "fleet serving: canary-window outcomes required before the "
       "CanaryController judges error-rate/p99 SLOs"),
    _k("MXNET_FLEET_CANARY_ERROR_RATE", 0.25, float, "wired",
       "fleet serving: canary error rate in excess of the baseline's "
       "(absolute) that triggers automatic rollback"),
    _k("MXNET_FLEET_CANARY_P99_FACTOR", 3.0, float, "wired",
       "fleet serving: canary p99 latency >= this multiple of the "
       "baseline's p99 triggers automatic rollback"),
    _k("MXNET_FLEET_WINDOW", 128, int, "wired",
       "fleet serving: per-lane sliding outcome window (requests) the "
       "canary SLO comparison runs over"),
    _k("MXNET_FLEET_DRAIN_TIMEOUT_MS", 10000.0, float, "wired",
       "fleet serving: bound on draining a retiring version's in-flight "
       "leases + batcher backlog before its lane is closed"),
    _k("MXNET_TRACE_ENABLE", 0, int, "wired",
       "record host-side spans from import (observability/tracer.py); "
       "profiler.set_state('run') enables tracing for its session "
       "regardless of this knob"),
    _k("MXNET_TRACE_BUFFER", 65536, int, "wired",
       "span ring-buffer capacity in events — full buffer drops the "
       "OLDEST record, so long runs trace at bounded memory"),
    _k("MXNET_TRACE_SAMPLE", 0.01, float, "wired",
       "tail sampler: random fraction of non-error traces kept "
       "(observability/telemetry.py TailSampler; error/deadline spans "
       "are always kept)"),
    _k("MXNET_TRACE_SAMPLE_BUDGET", 10.0, float, "wired",
       "tail sampler: token-bucket bound on random keeps per second so "
       "a traffic spike cannot explode the kept set (<=0 = no budget)"),
    _k("MXNET_TRACE_SLOW_MS", 0.0, float, "wired",
       "tail sampler: spans at/over this duration are kept like errors "
       "(latency anomalies; 0 = off)"),
    _k("MXNET_TELEMETRY_FLOPS", 1, int, "wired",
       "cache analytic FLOPs per CachedOp executable at compile time "
       "(XLA cost model) and account them per dispatch — the "
       "mxtpu_flops_total / mxtpu_mfu_percent source (cached_op.py)"),
    _k("MXNET_TELEMETRY_PEAK_FLOPS", 0.0, float, "wired",
       "per-device peak FLOP/s for MFU; 0 = use the built-in "
       "device-kind table (unknown kinds report no MFU rather than a "
       "made-up one)"),
    _k("MXNET_TELEMETRY_WINDOW_S", 60.0, float, "wired",
       "trailing window for the FLOP/s rate behind mxtpu_mfu_percent"),
    _k("MXNET_TELEMETRY_HEADROOM_MIN", 0.05, float, "wired",
       "degrade /healthz when any device's free-HBM fraction drops "
       "below this — the pre-OOM drain signal (<=0 disables)"),
    _k("MXNET_ENGINE_BULK_SIZE", 15, int, "wired",
       "engine bulk-dispatch size set via the C API "
       "(MXEngineSetBulkSize parity; _c_api_impl.py)"),
    _k("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS", 0.0, float, "wired",
       "only persist compiles at least this slow (0 = everything — "
       "jax's 1.0s default would skip the small serving-ladder rungs "
       "cold restarts stall on)"),
    _k("MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES", 0, int, "wired",
       "size floor per persistent-cache entry in bytes (0 = none)"),
    _k("MXNET_COMPILE_CACHE_TTL_DAYS", 0.0, float, "wired",
       "age out persistent-cache entries older than this at init "
       "(newest of write/last-use time; 0 = keep forever)"),
    _k("MXNET_WARMUP_THREADS", 4, int, "wired",
       "InferenceEngine warmup/prewarm compile concurrency: bucket "
       "rungs compile on a thread pool this wide (<=1 = serial; "
       "compiles already run outside CachedOp's dispatch lock)"),
    _k("MXNET_GATEWAY_SCRAPE_MS", 250.0, float, "wired",
       "gateway load/health scrape interval: how often serving/gateway.py "
       "fans out to every replica's /healthz + /metrics for the "
       "least-loaded routing signal (queue depth, breaker state, "
       "degraded health, HBM headroom)"),
    _k("MXNET_GATEWAY_CONNECT_TIMEOUT_MS", 1000.0, float, "wired",
       "gateway -> replica connect/read timeout for scrapes and the "
       "pre-response window of forwarded requests; a replica that "
       "cannot be reached inside it is a failover, not a client error"),
    _k("MXNET_GATEWAY_EJECT_FAILURES", 3, int, "wired",
       "consecutive forward failures before a replica's gateway-side "
       "circuit breaker ejects it from routing (<=0 disables ejection)"),
    _k("MXNET_GATEWAY_EJECT_RECOVERY_MS", 2000.0, float, "wired",
       "how long an ejected replica sits out before the breaker's "
       "half-open probe offers it one request to earn readmission"),
    _k("MXNET_GATEWAY_DRAIN_TIMEOUT_MS", 10000.0, float, "wired",
       "bound on waiting for a draining replica's in-flight requests "
       "and pinned streams to clear during rolling restart / scale-down"),
    _k("MXNET_GATEWAY_SLO_P99_MS", 500.0, float, "wired",
       "autoscaler latency SLO: sustained gateway-observed p99 above "
       "this burns the SLO budget and grows the replica set (0 "
       "disables the latency signal; queue depth still scales)"),
    _k("MXNET_GATEWAY_QUEUE_HIGH", 8, int, "wired",
       "autoscaler queue signal: mean scraped batcher queue depth per "
       "routable replica above this counts as a burn tick"),
    _k("MXNET_GATEWAY_MIN_REPLICAS", 1, int, "wired",
       "autoscaler floor: scale-down never drains below this many "
       "routable replicas"),
    _k("MXNET_GATEWAY_MAX_REPLICAS", 8, int, "wired",
       "autoscaler ceiling: scale-up stops here no matter the burn"),
    _k("MXNET_SERVING_ADMIN_TOKEN", "", str, "wired",
       "when set, admin endpoints (ModelServer GET /drain, POST "
       "/debug/profile) require a matching X-Admin-Token header; "
       "empty = unguarded (dev/tests)"),
    _k("MXNET_PLAN_HBM_BYTES", 0, int, "wired",
       "sharding planner per-device memory budget: placements whose "
       "modeled params+optimizer+activation bytes/device exceed it are "
       "infeasible (parallel/planner.py; 0 = unconstrained)"),
    _k("MXNET_PLAN_MAX_PP", 0, int, "wired",
       "sharding planner cap on the pipeline factor — bound the bubble "
       "fraction regardless of what the cost model prefers (0 = no cap)"),
    _k("MXNET_PLAN_FORCE", "", str, "wired",
       "bypass the placement search with an explicit plan, e.g. "
       "'dp=2,pp=2,ep=2' — still validated against the model profile "
       "(divisibility + memory gate)"),
    _k("MXNET_SERVE_PLAN_HBM_BYTES", 0, int, "wired",
       "serving planner per-device memory budget: placements whose "
       "modeled weights+activation+kv-arena bytes/device exceed it are "
       "infeasible for plan_serving (parallel/planner.py; 0 = "
       "unconstrained). Separate from MXNET_PLAN_HBM_BYTES because "
       "inference carries no optimizer state"),
    _k("MXNET_SERVE_PLAN_MAX_PP", 0, int, "wired",
       "serving planner cap on the pipeline factor for plan_serving "
       "(0 = no cap) — decode already prices pp's serialized hops, this "
       "forbids them outright"),
    _k("MXNET_SERVE_PLAN_FORCE", "", str, "wired",
       "bypass the serving placement search with an explicit plan, e.g. "
       "'dp=1,ep=8' — still validated against the model profile "
       "(divisibility + serving memory gate)"),
    _k("MXNET_PROF_ATTRIBUTION", 1, int, "wired",
       "per-executable roofline accounting: capture bytes-accessed from "
       "XLA cost analysis at compile time and measure per-dispatch wall "
       "time, aggregated per (op, signature) — the mxtpu_roofline_* / "
       "tools/roofline_report.py source (observability/attribution.py)"),
    _k("MXNET_PROF_HBM_GBPS", 0.0, float, "wired",
       "per-device HBM bandwidth in GB/s for the roofline ridge point; "
       "0 = use the built-in device-kind table (unknown kinds fall back "
       "to MXNET_PROF_RIDGE classification)"),
    _k("MXNET_PROF_RIDGE", 0.0, float, "wired",
       "arithmetic-intensity ridge point (FLOP/byte) separating "
       "hbm_bound from compute_bound when device peak/bandwidth are "
       "unknown (CPU oracle); 0 = the built-in v5e-like default"),
    _k("MXNET_PROF_OVERHEAD_FRACTION", 0.05, float, "wired",
       "roofline classification: executables achieving less than this "
       "fraction of their roofline ceiling are overhead_bound — "
       "dispatch/padding overhead, not the hardware, is the limiter"),
    _k("MXNET_PROF_CAPTURE_MAX_S", 60.0, float, "wired",
       "upper bound on POST /debug/profile?seconds=N capture length — "
       "an admin typo must not pin a serving thread for an hour"),
    _k("MXNET_PROF_DIR", "/tmp/mxnet_tpu_profiles", str, "wired",
       "base directory for on-demand profile capture artifacts "
       "(observability/attribution.py capture_profile)"),
    _k("MXNET_FLIGHT_RECORDER", 1, int, "wired",
       "always-on flight recorder: bounded ring of the last K step/"
       "request/dispatch/compile/guard-skip timing records, dumped as "
       "JSON on SIGUSR2, AnomalyFault/CollectiveTimeout, and watchdog "
       "stall (observability/attribution.py; 0 disables)"),
    _k("MXNET_FLIGHT_RECORDS", 256, int, "wired",
       "flight-recorder ring capacity in records (drop-oldest)"),
    _k("MXNET_FLIGHT_DIR", "/tmp/mxnet_tpu_flight", str, "wired",
       "directory flight-recorder dumps are written to"),
    # ---- subsumed by XLA/PJRT --------------------------------------------
    _k("MXNET_EXEC_BULK_EXEC_INFERENCE", 1, int, "subsumed",
       "XLA compiles whole programs; bulking is implicit"),
    _k("MXNET_EXEC_BULK_EXEC_TRAIN", 1, int, "subsumed",
       "XLA compiles whole programs; bulking is implicit"),
    _k("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN_FWD", -1, int, "subsumed",
       "see MXNET_EXEC_BULK_EXEC_TRAIN"),
    _k("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN_BWD", -1, int, "subsumed",
       "see MXNET_EXEC_BULK_EXEC_TRAIN"),
    _k("MXNET_EXEC_ENABLE_INPLACE", True, bool, "subsumed",
       "XLA buffer assignment + donation owns aliasing"),
    _k("MXNET_EXEC_NUM_TEMP", 1, int, "subsumed",
       "workspace memory is planned by XLA"),
    _k("MXNET_BACKWARD_DO_MIRROR", 0, int, "subsumed",
       "rematerialization = jax.checkpoint/remat policies"),
    _k("MXNET_ELIMINATE_COMMON_EXPR", 1, int, "subsumed", "XLA CSE pass"),
    _k("MXNET_USE_FUSION", 1, int, "subsumed", "XLA fusion pass"),
    _k("MXNET_FUSION_VERBOSE", 0, int, "subsumed",
       "use XLA_FLAGS dumping instead"),
    _k("MXNET_SUBGRAPH_BACKEND", "NONE", str, "wired",
       "subgraph partition backend applied at bind time "
       "(symbol/subgraph.py; e.g. TPU_ELEMWISE)"),
    _k("MXNET_GPU_MEM_POOL_TYPE", "Naive", str, "subsumed",
       "PJRT owns the device allocator"),
    _k("MXNET_GPU_MEM_POOL_RESERVE", 5, int, "subsumed",
       "PJRT owns the device allocator"),
    _k("MXNET_GPU_MEM_LARGE_ALLOC_ROUND_SIZE", 2 * 1024 * 1024, int,
       "subsumed", "PJRT owns the device allocator"),
    _k("MXNET_GPU_MEM_POOL_ROUND_LINEAR_CUTOFF", 24, int, "subsumed",
       "PJRT owns the device allocator"),
    _k("MXNET_GPU_WORKER_NTHREADS", 2, int, "subsumed",
       "PJRT stream executor owns device queues"),
    _k("MXNET_GPU_WORKER_NSTREAMS", 1, int, "subsumed",
       "PJRT stream executor owns device queues"),
    _k("MXNET_GPU_COPY_NTHREADS", 2, int, "subsumed",
       "PJRT owns transfer streams"),
    _k("MXNET_CPU_PRIORITY_NTHREADS", 4, int, "subsumed",
       "no priority op queue; XLA program order"),
    _k("MXNET_CPU_TEMP_COPY", 4, int, "subsumed", "PJRT transfer path"),
    _k("MXNET_GPU_TEMP_COPY", 1, int, "subsumed", "PJRT transfer path"),
    _k("MXNET_CPU_PARALLEL_RAND_COPY", 1, int, "subsumed",
       "PJRT transfer path"),
    _k("MXNET_GPU_PARALLEL_RAND_COPY", 4, int, "subsumed",
       "PJRT transfer path"),
    _k("MXNET_CPU_PARALLEL_COPY_SIZE", 200000, int, "subsumed",
       "PJRT transfer path"),
    _k("MXNET_OPTIMIZER_AGGREGATION_SIZE", 4, int, "subsumed",
       "optimizer updates are fused into the jitted step"),
    _k("MXNET_KVSTORE_REDUCTION_NTHREADS", 4, int, "subsumed",
       "reductions ride XLA collectives"),
    _k("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000, int, "subsumed",
       "no key sharding: one collective per tensor"),
    _k("MXNET_KVSTORE_USETREE", 0, int, "subsumed",
       "ICI torus topology handled by the XLA collective scheduler"),
    _k("MXNET_KVSTORE_LOGTREE", 0, int, "subsumed", "see USETREE"),
    _k("MXNET_KVSTORE_TREE_ARRAY_BOUND", 10000000, int, "subsumed",
       "see USETREE"),
    _k("MXNET_KVSTORE_TREE_BACKTRACK", 0, int, "subsumed", "see USETREE"),
    _k("MXNET_KVSTORE_TREE_LINK_USAGE_PENALTY", 0.7, float, "subsumed",
       "see USETREE"),
    # ---- n/a (CUDA / MKLDNN / Cython specifics) ---------------------------
    _k("MXNET_CUDNN_AUTOTUNE_DEFAULT", 1, int, "n/a",
       "XLA autotunes TPU kernels"),
    _k("MXNET_CUDA_ALLOW_TENSOR_CORE", 1, int, "n/a",
       "MXU bf16 is the native path"),
    _k("MXNET_CUDA_TENSOR_OP_MATH_ALLOW_CONVERSION", 0, int, "n/a",
       "use amp bf16 policies"),
    _k("MXNET_CUDA_LIB_CHECKING", 1, int, "n/a", "no CUDA libs"),
    _k("MXNET_CUDNN_LIB_CHECKING", 1, int, "n/a", "no cuDNN"),
    _k("MXNET_GPU_CUDNN_DROPOUT_STATE_COPY", 0, int, "n/a",
       "RNG keys are functional state here"),
    _k("MXNET_ENABLE_GPU_P2P", 1, int, "n/a", "ICI mesh instead of P2P"),
    _k("MXNET_CPU_NNPACK_NTHREADS", 4, int, "n/a", "no NNPACK"),
    _k("MXNET_MKLDNN_ENABLED", 1, int, "n/a", "no MKLDNN"),
    _k("MXNET_MKLDNN_CACHE_NUM", -1, int, "n/a", "no MKLDNN"),
    _k("MXNET_ENABLE_CYTHON", 1, int, "n/a", "pure python frontend"),
    _k("MXNET_ENFORCE_CYTHON", 0, int, "n/a", "pure python frontend"),
    _k("MXNET_LIBRARY_PATH", "", str, "n/a",
       "no dlopen'd accelerator libs; custom kernels register via "
       "mx.operator.register_op"),
    _k("MXNET_MP_WORKER_NTHREADS", 1, int, "wired",
       "worker threads per DataLoader worker (thread pool, not fork)"),
    _k("MXNET_MP_OPENCV_NUM_THREADS", 0, int, "n/a", "no OpenCV"),
])


def get(name, default=None):
    """Typed env read. Unknown names fall back to raw os.environ access
    (reference behavior: any MXNET_* var can be probed)."""
    knob = KNOBS.get(name)
    raw = os.environ.get(name)
    if knob is None:
        return raw if raw is not None else default
    if raw is None:
        return knob.default if default is None else default
    if knob.typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    try:
        return knob.typ(raw)
    except (TypeError, ValueError):
        return knob.default


def set(name, value):  # noqa: A001  (parity with reference os.environ use)
    os.environ[name] = str(value)


def describe():
    """Render the knob table (name, disposition, current, doc)."""
    lines = ["%-44s %-9s %-22s %s" % ("name", "status", "value", "doc")]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        lines.append("%-44s %-9s %-22r %s"
                     % (name, k.disposition, get(name), k.doc[:60]))
    return "\n".join(lines)
