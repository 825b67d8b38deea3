"""AOT executable artifacts: serialize compiled XLA programs to disk.

The cold-start killer (ROADMAP item 4): a served model's bucket ladder is
``len(buckets)`` XLA compiles at 1-30s each, paid again on every process
restart. TF-Serving's answer — SavedModel warmup assets shipped *with*
the model — is the shape followed here: :meth:`CachedOp.serialize
<mxnet_tpu.cached_op.CachedOp.serialize>` captures every resident
executable as PJRT-serialized bytes, this module packs them into one
checksummable container file (``executables.mxa``), and a restarting
process loads them back with **zero** XLA compiles.

Container format (version 1)::

    MAGIC (10 bytes)  "MXTPUAOT1\\0"
    header length     8-byte little-endian unsigned
    header JSON       {"format": 1, "fingerprint": {...}, "extra": {...},
                       "entries": [{"signature", "train", "flops",
                                    "devices", "in_tree_size",
                                    "out_tree_size", "blob_size"}, ...]}
    entry payloads    concatenated (in_tree pickle, out_tree pickle, blob)
                      in entry order

Every size is declared in the header, so :func:`read_artifact_header`
detects truncation by arithmetic alone — a corrupt or cut-off artifact
raises a typed :class:`ArtifactError` at *manifest verify* time, never as
a confusing PJRT failure on the first live request.

A serialized executable is machine code for one exact (backend, device
kind, device span, jax/jaxlib version): :func:`fingerprint` records that
tuple at export and :func:`fingerprint_matches` gates the load. Each
entry also records the ids of the devices its program was compiled for,
and is loaded onto exactly those — a one-chip artifact loads on a
four-chip host, onto the chip it names. A mismatch is never a crash —
callers fall back to a normal compile (the persistent compile cache then
usually still saves the XLA run).
"""
from __future__ import annotations

import json
import os
import pickle
import struct

__all__ = ["ArtifactError", "ARTIFACT_NAME", "WARMUP_NAME",
           "fingerprint", "mesh_axes", "fingerprint_matches",
           "fingerprint_diff",
           "write_artifact", "read_artifact", "read_artifact_header",
           "serialize_compiled", "deserialize_compiled"]

MAGIC = b"MXTPUAOT1\x00"
ARTIFACT_NAME = "executables.mxa"
WARMUP_NAME = "warmup.json"

# a single artifact header is metadata, not payload: a multi-gigabyte
# "header length" is a corrupt or hostile file, not a big model
_MAX_HEADER_BYTES = 64 << 20


class ArtifactError(Exception):
    """AOT artifact is corrupt, truncated, or structurally invalid —
    raised at manifest-verify/load time, never at first request."""


# ---------------------------------------------------------------------------
# fingerprinting: which process may load this artifact
# ---------------------------------------------------------------------------

def mesh_axes(mesh):
    """Normalize a mesh descriptor to the fingerprint's ``mesh`` entry:
    ordered ``{axis_name: size}`` from a ``jax.sharding.Mesh`` (its
    ``.shape`` mapping), a plain dict, or None (single-device lane).
    Size-1 axes are kept — the axis NAMES are part of what the compiled
    SPMD program was specialized against."""
    if mesh is None:
        return None
    shape = getattr(mesh, "shape", mesh)
    if not hasattr(shape, "items"):
        raise ArtifactError("mesh descriptor %r has no axis mapping"
                            % (mesh,))
    return {str(k): int(v) for k, v in shape.items()}


def fingerprint(mesh=None):
    """The compatibility tuple a serialized executable is valid for:
    jax/jaxlib/mxnet_tpu versions + backend platform + device kind +
    the number of devices the programs span + (for sharded lanes) the
    mesh axis names and sizes they were compiled against. Computed at
    export, compared at load.

    ``mesh=None`` means single-device programs (``n_devices`` 1,
    whatever the host holds); an artifact exported without a mesh can
    therefore never be silently installed into a sharded lane (and vice
    versa) — :func:`fingerprint_matches` treats ``mesh`` exactly like
    the topology keys. WHICH devices is per entry (``devices``)."""
    import math
    import jax
    import jaxlib
    from . import __version__ as _mx_version
    dev = jax.local_devices()[0]
    axes = mesh_axes(mesh)
    return {
        "format": 1,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "mxnet_tpu": _mx_version,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "") or "",
        "n_devices": math.prod(axes.values()) if axes else 1,
        "mesh": axes,
    }


# "mesh" compares via .get on BOTH sides: a pre-mesh artifact (no key)
# equals a current single-device fingerprint (mesh None) — old artifacts
# keep loading — while a sharded lane's mesh dict never equals either.
_COMPARED_KEYS = ("jax", "jaxlib", "platform", "device_kind", "n_devices",
                  "mesh")


def fingerprint_matches(recorded, current=None):
    """True when an artifact recorded under ``recorded`` may be loaded by
    this process. Strict on runtime version and topology (machine code),
    lenient on keys a future format may add."""
    if not isinstance(recorded, dict):
        return False
    current = current or fingerprint()
    return all(recorded.get(k) == current.get(k) for k in _COMPARED_KEYS)


def fingerprint_diff(recorded, current=None):
    """Human-readable ``key: recorded != current`` list for the
    fallback warning."""
    current = current or fingerprint()
    if not isinstance(recorded, dict):
        return ["fingerprint missing or malformed"]
    return ["%s: %r != %r" % (k, recorded.get(k), current.get(k))
            for k in _COMPARED_KEYS
            if recorded.get(k) != current.get(k)]


# ---------------------------------------------------------------------------
# per-executable serialization (jax AOT stages)
# ---------------------------------------------------------------------------

def serialize_compiled(compiled):
    """``jax.stages.Compiled`` → ``(blob, in_tree_bytes, out_tree_bytes,
    device_ids)``; ``device_ids`` are the devices the program was
    compiled for, in assignment order. Raises :class:`ArtifactError`
    when the backend's executables don't support serialization (the
    caller skips AOT export, it doesn't crash)."""
    from jax.experimental import serialize_executable as _se
    try:
        blob, in_tree, out_tree = _se.serialize(compiled)
        devices = [int(d.id) for d in
                   compiled.runtime_executable().local_devices()]
        return blob, pickle.dumps(in_tree), pickle.dumps(out_tree), devices
    except Exception as exc:  # noqa: BLE001 — typed for callers
        raise ArtifactError(
            "backend cannot serialize compiled executable: %s: %s"
            % (type(exc).__name__, exc)) from exc


def deserialize_compiled(blob, in_tree_bytes, out_tree_bytes, device_ids):
    """Inverse of :func:`serialize_compiled`: bytes → a callable
    ``jax.stages.Compiled`` loaded onto the devices it was compiled for
    (``device_ids``; left to itself jax loads onto EVERY local device and
    a one-chip program then refuses its one-shard arguments). A device
    this host does not have is an :class:`ArtifactError`. No XLA compile
    happens here — PJRT deserializes machine code."""
    import jax
    from jax.experimental import serialize_executable as _se
    try:
        by_id = {d.id: d for d in jax.local_devices()}
        devices = [by_id[i] for i in device_ids]
        in_tree = pickle.loads(in_tree_bytes)
        out_tree = pickle.loads(out_tree_bytes)
        return _se.deserialize_and_load(blob, in_tree, out_tree,
                                        execution_devices=devices)
    except Exception as exc:  # noqa: BLE001 — typed for callers
        raise ArtifactError(
            "cannot deserialize executable blob: %s: %s"
            % (type(exc).__name__, exc)) from exc


# ---------------------------------------------------------------------------
# the container file
# ---------------------------------------------------------------------------

def _jsonable_signature(sig):
    """Cache signature tuple → JSON structure (tuples become lists)."""
    shapes, train = sig
    return {"inputs": [[list(shape), str(dtype)] for shape, dtype in shapes],
            "train": bool(train)}


def signature_from_json(obj):
    """JSON structure → the exact cache-key tuple ``CachedOp`` uses."""
    return (tuple((tuple(int(d) for d in shape), str(dtype))
                  for shape, dtype in obj["inputs"]),
            bool(obj["train"]))


def write_artifact(path, records, extra=None, fp=None):
    """Write ``records`` (from ``CachedOp.serialize``) as one artifact
    file, atomically (staged to ``<path>.tmp``, then renamed — the
    checkpoint-publish idiom, so a crash mid-export never leaves a
    half-artifact that passes a later existence check).

    ``records``: list of dicts with keys ``signature`` (cache-key tuple),
    ``train``, ``flops``, ``devices``, ``blob``, ``in_tree``, ``out_tree``.
    ``extra`` lands in the header verbatim (the engine records its bucket
    ladder there). Returns the header dict."""
    if not records:
        raise ArtifactError("refusing to write an artifact with zero "
                            "executables (nothing compiled yet?)")
    entries = []
    payloads = []
    for rec in records:
        entries.append({
            "signature": _jsonable_signature(rec["signature"]),
            "train": bool(rec["train"]),
            "flops": float(rec.get("flops") or 0.0),
            "devices": [int(i) for i in rec["devices"]],
            "in_tree_size": len(rec["in_tree"]),
            "out_tree_size": len(rec["out_tree"]),
            "blob_size": len(rec["blob"]),
        })
        payloads.append(rec["in_tree"] + rec["out_tree"] + rec["blob"])
    header = {"format": 1,
              "fingerprint": fp or fingerprint(),
              "extra": dict(extra or {}),
              "entries": entries}
    header_bytes = json.dumps(header, sort_keys=True).encode("utf-8")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<Q", len(header_bytes)))
        f.write(header_bytes)
        for p in payloads:
            f.write(p)
        f.flush()
        os.fsync(f.fileno())
    os.rename(tmp, path)
    return header


def _entry_size(e):
    try:
        return (int(e["in_tree_size"]) + int(e["out_tree_size"])
                + int(e["blob_size"]))
    except (KeyError, TypeError, ValueError) as exc:
        raise ArtifactError("artifact entry metadata malformed: %s"
                            % (exc,)) from exc


def read_artifact_header(path):
    """Parse and structurally validate an artifact's header WITHOUT
    loading any executable: magic, header JSON, and declared-vs-actual
    file size (truncation shows up as arithmetic, not as a PJRT error on
    the first request). Raises :class:`ArtifactError`; returns the
    header dict."""
    try:
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            magic = f.read(len(MAGIC))
            if magic != MAGIC:
                raise ArtifactError(
                    "%s: bad magic %r — not an mxnet_tpu AOT artifact "
                    "(or truncated inside the magic)" % (path, magic))
            raw_len = f.read(8)
            if len(raw_len) != 8:
                raise ArtifactError("%s: truncated before header length"
                                    % path)
            (header_len,) = struct.unpack("<Q", raw_len)
            if header_len <= 0 or header_len > _MAX_HEADER_BYTES:
                raise ArtifactError("%s: implausible header length %d"
                                    % (path, header_len))
            header_bytes = f.read(header_len)
            if len(header_bytes) != header_len:
                raise ArtifactError("%s: truncated inside header "
                                    "(%d of %d bytes)"
                                    % (path, len(header_bytes), header_len))
    except OSError as exc:
        raise ArtifactError("%s: unreadable artifact: %s"
                            % (path, exc)) from exc
    try:
        header = json.loads(header_bytes.decode("utf-8"))
    except (ValueError, UnicodeDecodeError) as exc:
        raise ArtifactError("%s: corrupt header JSON: %s"
                            % (path, exc)) from exc
    if header.get("format") != 1:
        raise ArtifactError("%s: unsupported artifact format %r"
                            % (path, header.get("format")))
    entries = header.get("entries")
    if not isinstance(entries, list) or not entries:
        raise ArtifactError("%s: artifact lists no executables" % path)
    expected = len(MAGIC) + 8 + header_len \
        + sum(_entry_size(e) for e in entries)
    if size != expected:
        raise ArtifactError(
            "%s: file is %d bytes, header declares %d (truncated or "
            "partially written)" % (path, size, expected))
    return header


def read_artifact(path):
    """Read the full artifact: ``(header, records)`` where each record is
    ``{"signature", "train", "flops", "devices", "blob", "in_tree",
    "out_tree"}`` ready for ``CachedOp.deserialize``. Raises :class:`ArtifactError` on
    any structural problem."""
    header = read_artifact_header(path)
    records = []
    with open(path, "rb") as f:
        f.seek(len(MAGIC))
        (header_len,) = struct.unpack("<Q", f.read(8))
        f.seek(len(MAGIC) + 8 + header_len)
        for e in header["entries"]:
            in_tree = f.read(int(e["in_tree_size"]))
            out_tree = f.read(int(e["out_tree_size"]))
            blob = f.read(int(e["blob_size"]))
            if len(blob) != int(e["blob_size"]):
                raise ArtifactError("%s: truncated executable payload"
                                    % path)
            records.append({
                "signature": signature_from_json(e["signature"]),
                "train": bool(e["train"]),
                "flops": float(e.get("flops") or 0.0),
                "devices": [int(i) for i in e["devices"]],
                "blob": blob, "in_tree": in_tree, "out_tree": out_tree,
            })
    return header, records
