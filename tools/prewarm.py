#!/usr/bin/env python
"""Build and gate AOT serving artifacts — compile once in CI, ship bytes.

The cold-start runbook (ROADMAP item 4, ``docs/performance.md``):

1. **Export** (CI, after training publishes a model version dir holding
   ``<prefix>-symbol.json`` + params): compile the bucket ladder here —
   the one place the compile storm is acceptable — and serialize every
   executable into ``executables.mxa``, plus the ``warmup.json`` replay
   manifest and an updated ``manifest.json`` whose checksummed
   ``executables`` section records what the blob is for::

       python tools/prewarm.py MODEL_DIR --example-shape 3,224,224
       python tools/prewarm.py MODEL_DIR --from-traffic warmup.json

   ``--from-traffic`` replays a warmup manifest captured from live
   traffic (``InferenceEngine.write_warmup_manifest`` on a serving
   host) instead of synthesizing one zero batch per bucket — the
   exported ladder then matches what production actually runs.

2. **Check** (CI gate: "artifacts shipped with the checkpoint")::

       python tools/prewarm.py MODEL_DIR --check
       python tools/prewarm.py MODEL_DIR --check --mesh dp=1,ep=8

   Exit 0 when the version dir's manifest lists executables, every
   checksum verifies, and the artifact's fingerprint matches THIS
   process (jax/jaxlib version, platform, device kind/count — and,
   for sharded artifacts, the ``--mesh`` expectation: the axis
   names+sizes the deployment will form). Exit 2 when artifacts are
   missing, stale, or mesh-drifted (re-export needed), 3 when they
   are corrupt. A restarting server would fall back to fresh compiles
   in exactly the cases this gate reports — the gate exists so that
   fallback never ships silently.

A serving restart then loads the artifacts (``ModelServer
(artifacts_dir=...)``, ``ModelRegistry.load(path=...)``) and compiles
nothing; see ``benchmark/coldstart_bench.py`` for the measured paths.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def _parse_mesh(spec):
    """``--mesh`` expectation string -> ordered ``{axis: size}`` dict.
    ``"dp=1,ep=8"`` -> ``{"dp": 1, "ep": 8}``; ``"none"`` / ``"single"``
    / ``""`` mean "expect an UNsharded artifact" (mesh ``None``)."""
    if spec is None:
        return None
    spec = spec.strip()
    if spec.lower() in ("", "none", "single"):
        return None
    mesh = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise SystemExit("--mesh expects 'axis=size,...' (e.g. "
                             "'dp=1,ep=8'), got %r" % part)
        k, v = part.split("=", 1)
        mesh[k.strip()] = int(v)
    return mesh or None


def check(model_dir, mesh=None):
    """The ``--check`` gate. Returns (exit_code, report dict).

    ``mesh`` is the deployment's mesh expectation (``--mesh``): the
    ordered axis dict the serving lane will form, or None for a
    single-chip lane. A sharded artifact records the mesh it was
    compiled against in its fingerprint; drift against the expectation
    — a single-chip artifact where the fleet plans a mesh, a
    dp1·ep8 artifact where the surviving pool can only form ep4 —
    exits 2 (``mesh-drift``) exactly like any other staleness, because
    the restarting replica would fall back to fresh compiles."""
    from mxnet_tpu import aot
    from mxnet_tpu.serving.fleet import (MANIFEST_NAME, ChecksumMismatch,
                                         ManifestError, verify_manifest)
    report = {"model_dir": os.path.abspath(model_dir), "status": "ok"}
    manifest_path = os.path.join(model_dir, MANIFEST_NAME)
    if not os.path.exists(manifest_path):
        report.update(status="missing",
                      error="no %s — run the export step" % MANIFEST_NAME)
        return 2, report
    try:
        manifest = verify_manifest(model_dir)
    except ChecksumMismatch as exc:
        report.update(status="corrupt", error=str(exc))
        return 3, report
    except aot.ArtifactError as exc:
        report.update(status="corrupt", error=str(exc))
        return 3, report
    except ManifestError as exc:
        report.update(status="missing", error=str(exc))
        return 2, report
    exe = manifest.get("executables")
    if not exe:
        report.update(status="missing",
                      error="manifest has no executables section — "
                            "artifacts were not exported for this version")
        return 2, report
    current = aot.fingerprint(mesh)
    recorded = exe.get("fingerprint")
    # the --mesh expectation is operator shorthand: a sharded lane always
    # forms the full named mesh, so axes the spec omits materialize at
    # size 1. If the recorded mesh agrees with the expectation on every
    # axis of size > 1 (both ways), adopt the recorded axis set — the
    # load-time fingerprint stays strict, only the CLI gate is lenient.
    rec_mesh = (recorded or {}).get("mesh")
    if current["mesh"] is not None and rec_mesh is not None:
        def _nontrivial(m):
            return {k: v for k, v in m.items() if v != 1}
        if _nontrivial(current["mesh"]) == _nontrivial(rec_mesh):
            current["mesh"] = dict(rec_mesh)
    report["executables"] = {"count": exe.get("count"),
                             "buckets": exe.get("buckets"),
                             "warmup": exe.get("warmup")}
    for k in ("engine", "mesh", "plan", "families"):
        if exe.get(k) is not None:
            report["executables"][k] = exe[k]
    report["fingerprint"] = {"recorded": recorded, "current": current}
    if not aot.fingerprint_matches(recorded, current):
        diff = aot.fingerprint_diff(recorded, current)
        # the device span follows from the mesh
        mesh_drift = all(d.startswith(("mesh:", "n_devices:"))
                         for d in diff)
        report.update(
            status="mesh-drift" if mesh_drift else "stale",
            error="artifact fingerprint does not match this process: %s "
                  "— re-export on the current topology/jax version"
                  % "; ".join(diff))
        if mesh_drift:
            rec_mesh = (recorded or {}).get("mesh")
            report["error"] = (
                "mesh drift: artifact compiled for mesh %r, deployment "
                "expects %r — a replica restarting on this plan would "
                "fall back to fresh compiles; re-export on the planned "
                "mesh" % (rec_mesh, current["mesh"]))
        return 2, report
    return 0, report


def export(model_dir, prefix, input_names, buckets, example_shape, dtype,
           from_traffic):
    """Compile the ladder and publish artifacts + manifest. Returns the
    report dict (raises on failure — CI wants the traceback)."""
    import numpy as np

    from mxnet_tpu.serving import InferenceEngine
    from mxnet_tpu.serving.fleet import write_manifest
    engine = InferenceEngine.load(
        os.path.join(model_dir, prefix), input_names=tuple(input_names),
        buckets=buckets, name="prewarm.export")
    if from_traffic is not None:
        _log("replaying traffic manifest %s ..." % from_traffic)
        engine.prewarm(manifest=from_traffic, background=False)
    else:
        if example_shape is None:
            raise SystemExit("need --example-shape (non-batch dims of one "
                             "input) or --from-traffic WARMUP_JSON")
        examples = [np.zeros((1,) + tuple(s), dtype=dtype)
                    for s in example_shape]
        _log("warming ladder %s over example shapes %s ..."
             % (list(buckets), [e.shape[1:] for e in examples]))
        engine.warmup(examples if len(examples) > 1 else examples[0])
    header = engine.export_artifacts(model_dir)
    manifest = write_manifest(model_dir)
    return {
        "model_dir": os.path.abspath(model_dir),
        "executables": len(header["entries"]),
        "buckets": header["extra"].get("buckets"),
        "fingerprint": header["fingerprint"],
        "warmup_manifest": manifest.get("executables", {}).get("warmup"),
        "status": "exported",
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="export / gate AOT serving artifacts for a model "
                    "version directory")
    ap.add_argument("model_dir", help="version directory holding "
                                      "<prefix>-symbol.json + params")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit non-zero when the manifest's "
                         "executables are missing/stale (2) or corrupt "
                         "(3) vs the current fingerprint")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help="with --check: the deployment's mesh "
                         "expectation, e.g. 'dp=1,ep=8' (or 'none' for "
                         "a single-chip lane, the default) — a sharded "
                         "artifact whose recorded mesh differs exits 2 "
                         "(mesh drift)")
    ap.add_argument("--prefix", default="model",
                    help="artifact prefix (default: model)")
    ap.add_argument("--input-names", default="data",
                    help="comma-separated model input names")
    ap.add_argument("--buckets", default="1,2,4,8,16,32",
                    help="batch-size ladder to compile (default: "
                         "1,2,4,8,16,32)")
    ap.add_argument("--example-shape", default=None,
                    help="non-batch dims of each input, ';'-separated "
                         "per input, ','-separated dims — e.g. "
                         "'3,224,224' or '128;128'")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--from-traffic", default=None, metavar="WARMUP_JSON",
                    help="replay a captured warmup manifest instead of "
                         "synthesizing one batch per bucket")
    args = ap.parse_args(argv)

    if args.check:
        code, report = check(args.model_dir, mesh=_parse_mesh(args.mesh))
        print(json.dumps(report, indent=2, sort_keys=True))
        return code

    example_shape = None
    if args.example_shape:
        example_shape = [tuple(int(d) for d in part.split(",") if d)
                         for part in args.example_shape.split(";")]
    report = export(
        args.model_dir, args.prefix,
        [n.strip() for n in args.input_names.split(",") if n.strip()],
        tuple(int(b) for b in args.buckets.split(",") if b),
        example_shape, args.dtype, args.from_traffic)
    print(json.dumps(report, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
