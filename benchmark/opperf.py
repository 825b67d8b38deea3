"""Per-operator forward/backward latency harness.

The committed benchmark/OPPERF.json artifact is the CPU-oracle sweep
(``"platform"`` is recorded inside); rerun ``--all`` on a TPU host for
chip latencies — the timing protocol (jit + D2H scalar sync) is
platform-correct either way.

Role parity: reference ``benchmark/opperf/opperf.py`` (per-op fwd/bwd
latency across the registry, SURVEY §6). TPU-native notes: each op is
timed as a jitted program (steady-state, compile excluded) and synced via
a device→host scalar read. Backward latency times jax.grad of a
sum-reduced call.

Usage::

    python benchmark/opperf.py                  # default op set
    python benchmark/opperf.py relu dot softmax # named ops
    python benchmark/opperf.py --json           # machine-readable lines
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


DEFAULT_OPS = ["relu", "sigmoid", "tanh", "exp", "softmax", "log_softmax",
               "sum", "mean", "max", "dot", "batch_dot", "transpose",
               "broadcast_add", "broadcast_mul", "take", "one_hot",
               "FullyConnected", "Convolution", "Pooling", "BatchNorm",
               "LayerNorm"]


def _inputs_for(name, n):
    """Representative inputs per op family (reference opperf's default
    shapes)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    t = lambda *s: jnp.asarray(rng.random(s).astype("float32"))
    if name == "dot":
        return (t(n, n), t(n, n)), {}
    if name == "batch_dot":
        return (t(8, n, n), t(8, n, n)), {}
    if name in ("broadcast_add", "broadcast_mul"):
        return (t(n, n), t(1, n)), {}
    if name == "take":
        return (t(n, n),
                jnp.asarray(rng.integers(0, n, (n,)).astype("int32"))), {}
    if name == "one_hot":
        return (jnp.asarray(rng.integers(0, n, (n,)).astype("int32")),), \
            {"depth": n}
    if name == "FullyConnected":
        return (t(64, n), t(n, n)), {"no_bias": True, "num_hidden": n}
    if name == "Convolution":
        return (t(8, 32, 64, 64), t(64, 32, 3, 3)), \
            {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1),
             "no_bias": True}
    if name == "Pooling":
        return (t(8, 32, 64, 64),), {"kernel": (2, 2), "stride": (2, 2),
                                     "pool_type": "max"}
    if name == "BatchNorm":
        return (t(8, 32, 32, 32), t(32), t(32), t(32), t(32)), \
            {"fix_gamma": False}
    if name == "LayerNorm":
        return (t(64, n), t(n), t(n)), {}
    if name in ("sum", "mean", "max", "transpose"):
        return (t(n, n),), {}
    return (t(n, n),), {}


def bench_op(name, n=512, reps=20):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op

    op = get_op(name)
    if op is None:
        raise SystemExit("unknown op %r" % name)
    args, kwargs = _inputs_for(name, n)

    fwd = jax.jit(lambda *a: op.fn(*a, **kwargs))

    def sync(x):
        while isinstance(x, (tuple, list)):
            x = x[0]
        return jax.device_get(jnp.ravel(x)[0])

    sync(fwd(*args))          # compile
    sync(fwd(*args))          # steady state
    t0 = time.perf_counter()
    r = None
    for _ in range(reps):
        r = fwd(*args)
    sync(r)
    fwd_ms = (time.perf_counter() - t0) / reps * 1e3

    bwd_ms = None

    def loss(*a):
        out = op.fn(*a, **kwargs)
        while isinstance(out, (tuple, list)):
            out = out[0]
        return jnp.sum(out.astype(jnp.float32))

    # differentiate w.r.t. every float input (data AND weights — dW is
    # the dominant backward cost for conv/dense)
    argnums = tuple(i for i, a in enumerate(args)
                    if jnp.issubdtype(a.dtype, jnp.floating))
    if not argnums:
        return fwd_ms, None
    try:
        grad = jax.jit(jax.grad(loss, argnums=argnums))
        sync(grad(*args))
    except TypeError:
        return fwd_ms, None  # genuinely non-differentiable op
    except Exception as e:  # real failure: surface it, don't report n/a
        print("WARNING: backward of %s failed: %s" % (name, e),
              file=sys.stderr)
        return fwd_ms, None
    sync(grad(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = grad(*args)
    sync(r)
    bwd_ms = (time.perf_counter() - t0) / reps * 1e3
    return fwd_ms, bwd_ms


def _generic_inputs(name, n):
    """Candidate generic input sets for the registry sweep, tried in
    order (the reference opperf maintains hand-written shapes per op
    family in nd_operations/*.py; a candidate ladder gets systematic
    coverage without 400 hand entries)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)

    def t(*s):
        return jnp.asarray(rng.random(s).astype("float32") + 0.1)

    def idx(*s):
        return jnp.asarray(rng.integers(0, 4, s).astype("int32"))

    return [
        ((t(n, n),), {}),
        ((t(n, n), t(n, n)), {}),
        ((t(8, n),), {}),
        ((t(8, 4, n),), {}),
        ((t(n, n), t(n, n), t(n, n)), {}),
        ((t(8, 8, 16, 16),), {}),
        ((t(n, n), idx(n)), {}),
        ((idx(n),), {}),
        ((t(n),), {}),
    ]


def sweep_registry(n=128, reps=5, out_path=None):
    """Time fwd/bwd of EVERY registered operator (reference opperf.py
    run_all_mxnet_operator_benchmarks role); ops whose generic inputs
    don't apply are recorded as skipped with the reason — the artifact
    reports coverage, not silence."""
    import jax
    from mxnet_tpu.ops.registry import list_ops, get_op

    names = sorted({get_op(nm).name for nm in list_ops()})
    rows = []
    n_ok = 0
    for name in names:
        op = get_op(name)
        candidates = []
        try:
            candidates.append(_inputs_for(name, n)
                              if name in DEFAULT_OPS else None)
        except Exception:
            pass
        cands = [c for c in candidates if c] + _generic_inputs(name, n)
        # resolve state binders (RNG key / train flag) the way invoke()
        # does, so samplers and dropout-family ops are timeable
        bound = {}
        for bk, binder in (op.state_binders or {}).items():
            try:
                bound[bk] = binder()
            except Exception:
                pass
        row = {"op": name, "status": "skip", "fwd_ms": None,
               "bwd_ms": None}
        for args_, kw0 in cands:
            kwargs_ = dict(kw0, **bound)
            try:
                fwd = jax.jit(lambda *a: op.fn(*a, **kwargs_))
                jax.eval_shape(fwd, *args_)
            except Exception as e:
                row["error"] = str(e)[:120]
                continue
            try:
                fwd_ms, bwd_ms = _time_callable(op, args_, kwargs_, reps)
            except Exception as e:
                row["error"] = str(e)[:120]
                continue
            row.update(status="ok", fwd_ms=round(fwd_ms, 4),
                       bwd_ms=(round(bwd_ms, 4)
                               if bwd_ms is not None else None))
            row.pop("error", None)
            n_ok += 1
            break
        rows.append(row)
        print("%-40s %s  fwd=%s bwd=%s"
              % (name, row["status"], row["fwd_ms"], row["bwd_ms"]),
              file=sys.stderr)
    artifact = {"n": n, "reps": reps,
                "platform": _platform_name(),
                "total_ops": len(names), "timed_ops": n_ok,
                "rows": rows}
    from benchmark._artifact import stamp
    artifact = stamp(artifact, platform=artifact["platform"])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print("wrote %s: %d/%d ops timed"
              % (out_path, n_ok, len(names)), file=sys.stderr)
    return artifact


def _platform_name():
    import jax
    return jax.devices()[0].platform


def _time_callable(op, args_, kwargs_, reps):
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda *a: op.fn(*a, **kwargs_))

    def sync(x):
        while isinstance(x, (tuple, list)):
            x = x[0]
        return jax.device_get(jnp.ravel(x)[0])

    sync(fwd(*args_))
    sync(fwd(*args_))
    t0 = time.perf_counter()
    r = None
    for _ in range(reps):
        r = fwd(*args_)
    sync(r)
    fwd_ms = (time.perf_counter() - t0) / reps * 1e3

    bwd_ms = None
    if op.differentiable:
        def loss(*a):
            out = op.fn(*a, **kwargs_)
            while isinstance(out, (tuple, list)):
                out = out[0]
            return jnp.sum(out.astype(jnp.float32))

        argnums = tuple(i for i, a in enumerate(args_)
                        if jnp.issubdtype(a.dtype, jnp.floating))
        if argnums:
            try:
                grad = jax.jit(jax.grad(loss, argnums=argnums))
                sync(grad(*args_))
                t0 = time.perf_counter()
                for _ in range(reps):
                    r = grad(*args_)
                sync(r)
                bwd_ms = (time.perf_counter() - t0) / reps * 1e3
            except Exception as e:
                # a crashed backward on a differentiable op is a finding,
                # not silence (the artifact stays ok/fwd-only, stderr
                # carries the reason)
                print("WARNING: backward of %s failed: %s"
                      % (op.name, str(e)[:160]), file=sys.stderr)
                bwd_ms = None
    return fwd_ms, bwd_ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("ops", nargs="*", default=None)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("-n", type=int, default=512, help="problem size")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--all", action="store_true",
                    help="sweep the ENTIRE op registry and write an "
                         "artifact (benchmark/OPPERF.json)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "OPPERF.json"))
    args = ap.parse_args()
    if args.all:
        # sweep defaults are smaller than the single-op defaults; honor
        # explicit flags, only downscale the UNSET argparse defaults
        n = 128 if args.n == 512 else args.n
        reps = 5 if args.reps == 20 else args.reps
        sweep_registry(n=n, reps=reps, out_path=args.out)
        return
    ops = args.ops or DEFAULT_OPS
    for name in ops:
        fwd_ms, bwd_ms = bench_op(name, n=args.n, reps=args.reps)
        if args.json:
            print(json.dumps({"op": name, "fwd_ms": round(fwd_ms, 4),
                              "bwd_ms": (round(bwd_ms, 4)
                                         if bwd_ms is not None else None)}))
        else:
            bwd = "%8.3f" % bwd_ms if bwd_ms is not None else "     n/a"
            print("%-18s fwd %8.3f ms   bwd %s ms" % (name, fwd_ms, bwd))


if __name__ == "__main__":
    main()
