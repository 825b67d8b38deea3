"""Cold-start tests — persistent compile cache, AOT artifacts, prewarm
(ISSUE 10).

Acceptance criteria covered on the CPU oracle:
(a) zero-compile restart: a ladder exported with
    ``InferenceEngine.export_artifacts`` loads back into a fresh engine
    with ``cache_stats()["compiles"] == 0`` and bitwise-equal outputs;
(b) fingerprint mismatch (different jax version / topology / ladder)
    falls back to fresh compiles with a warn-once and a counted
    ``cachedop.pcache.fallback`` row — never a crash;
(c) a corrupt or truncated artifact raises a typed ``ArtifactError`` at
    manifest-verify time, not at first request;
plus the satellites: parallel warmup, traffic-ordered prewarm manifests,
the background prewarm thread, ``tools/prewarm.py --check`` exit codes,
and the fleet manifest's checksummed ``executables`` section.
"""
import hashlib
import importlib.util
import json
import os
import time
import urllib.request
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot, nd, pcache
from mxnet_tpu.cached_op import CachedOp, cache_stats, reset_cache_stats
from mxnet_tpu.serving import InferenceEngine, ModelRegistry, ModelServer
from mxnet_tpu.serving.fleet import (MANIFEST_NAME, ChecksumMismatch,
                                     verify_manifest, write_manifest)

D_IN, D_OUT = 8, 3
_W = np.linspace(-1, 1, D_IN * D_OUT).reshape(D_IN, D_OUT).astype("float32")


def _linear(x):
    return nd.dot(x, nd.array(_W))


@pytest.fixture(autouse=True)
def _fresh_ledger():
    pcache.reset_stats()
    reset_cache_stats()
    yield
    pcache.reset_stats()


def _exported_dir(tmp_path, buckets=(1, 2)):
    """A published model version dir: symbol+params, warmed ladder,
    AOT artifacts, checksummed manifest."""
    net = mx.gluon.nn.Dense(D_OUT, in_units=D_IN)
    net.initialize()
    path = str(tmp_path / "v1")
    os.makedirs(path, exist_ok=True)
    net.export(os.path.join(path, "model"))
    eng = InferenceEngine.load(os.path.join(path, "model"),
                               buckets=buckets, name="coldstart.export")
    eng.warmup(np.zeros((1, D_IN), "float32"))
    eng.export_artifacts(path)
    write_manifest(path)
    return path, net


# ---------------------------------------------------------------------------
# aot: container format + fingerprint gating
# ---------------------------------------------------------------------------

def _fake_records():
    return [{"signature": ((((2, 3), "float32"),), False), "train": False,
             "flops": 12.0, "devices": [0], "blob": b"B" * 40,
             "in_tree": b"I" * 7, "out_tree": b"O" * 9}]


def test_artifact_roundtrip_and_header_validation(tmp_path):
    path = str(tmp_path / "a.mxa")
    header = aot.write_artifact(path, _fake_records(), extra={"k": 1})
    assert header["entries"][0]["blob_size"] == 40
    got_header, records = aot.read_artifact(path)
    assert got_header["extra"] == {"k": 1}
    assert records[0]["signature"] == ((((2, 3), "float32"),), False)
    assert records[0]["blob"] == b"B" * 40
    assert records[0]["in_tree"] == b"I" * 7
    # the structural check reads no payload
    assert aot.read_artifact_header(path)["entries"][0]["flops"] == 12.0
    with pytest.raises(aot.ArtifactError):
        aot.write_artifact(str(tmp_path / "empty.mxa"), [])


def test_artifact_truncation_and_corruption_are_typed(tmp_path):
    path = str(tmp_path / "a.mxa")
    aot.write_artifact(path, _fake_records())
    blob = open(path, "rb").read()
    # truncated payload: size arithmetic catches it without PJRT
    open(path, "wb").write(blob[:-5])
    with pytest.raises(aot.ArtifactError, match="truncated|declares"):
        aot.read_artifact_header(path)
    # bad magic: not ours
    open(path, "wb").write(b"GARBAGE" + blob[7:])
    with pytest.raises(aot.ArtifactError, match="magic"):
        aot.read_artifact_header(path)
    # corrupt header JSON
    cut = len(aot.MAGIC) + 8
    open(path, "wb").write(blob[:cut] + b"{" * 20 + blob[cut + 20:])
    with pytest.raises(aot.ArtifactError, match="header"):
        aot.read_artifact_header(path)


def test_fingerprint_match_and_diff():
    fp = aot.fingerprint()
    assert aot.fingerprint_matches(fp)
    assert fp["platform"] == "cpu"
    stale = dict(fp, jax="0.0.0")
    assert not aot.fingerprint_matches(stale)
    assert any("jax" in d for d in aot.fingerprint_diff(stale))
    assert not aot.fingerprint_matches(None)
    assert not aot.fingerprint_matches({"format": 1})


# ---------------------------------------------------------------------------
# CachedOp: serialize/deserialize, zero compiles, autograd guard
# ---------------------------------------------------------------------------

def test_cachedop_serialize_deserialize_zero_compile():
    op = CachedOp(_linear, name="cs.op")
    x = nd.array(np.random.RandomState(0).randn(2, D_IN).astype("float32"))
    ref = op(x).asnumpy()
    records = op.serialize()
    assert len(records) == 1 and records[0]["signature"][1] is False

    op2 = CachedOp(_linear, name="cs.op2")
    reset_cache_stats()
    assert op2.deserialize(records) == 1
    out = op2(x).asnumpy()
    st = op2.cache_stats()
    assert st["misses"] == 0 and st["aot_loads"] == 1 and st["hits"] == 1
    assert cache_stats()["misses"] == 0      # no process-wide compile either
    np.testing.assert_array_equal(out, ref)
    assert pcache.stats()["aot_loads"] == 1


def test_aot_entry_loads_onto_the_devices_it_was_compiled_for():
    """A one-device program on a many-device host (the suite's 8 virtual
    devices stand in for a four-chip host): the record names its device,
    the load goes there and nowhere else, and a device this host lacks
    is a typed ArtifactError, not a dispatch-time surprise."""
    import jax
    op = CachedOp(_linear, name="cs.dev")
    op(nd.array(np.ones((2, D_IN), "float32")))
    (rec,) = op.serialize()
    assert rec["devices"] == [jax.devices()[0].id]
    assert aot.fingerprint()["n_devices"] == 1 < len(jax.devices())
    exe = aot.deserialize_compiled(rec["blob"], rec["in_tree"],
                                   rec["out_tree"], rec["devices"])
    assert exe.runtime_executable().local_devices() == [jax.devices()[0]]
    with pytest.raises(aot.ArtifactError):
        aot.deserialize_compiled(rec["blob"], rec["in_tree"],
                                 rec["out_tree"], [10 ** 6])


def test_cachedop_aot_entry_recompiles_under_recording():
    op = CachedOp(_linear, name="cs.rec")
    x = nd.array(np.ones((2, D_IN), "float32"))
    op(x)
    op2 = CachedOp(_linear, name="cs.rec2")
    op2.deserialize(op.serialize())
    assert op2.cache_stats()["misses"] == 0
    # machine code can't be retraced for the tape: recording dispatch
    # replaces the AOT entry with a fresh traceable compile
    with mx.autograd.record():
        out = op2(x)
    assert op2.cache_stats()["misses"] == 1
    np.testing.assert_allclose(out.asnumpy(), np.ones((2, D_IN)) @ _W,
                               rtol=1e-5, atol=1e-6)
    # and the replacement entry serves non-recording dispatch as a hit
    hits_before = op2.cache_stats()["hits"]
    op2(x)
    assert op2.cache_stats()["hits"] == hits_before + 1


# ---------------------------------------------------------------------------
# InferenceEngine: export/load artifacts, fallback paths
# ---------------------------------------------------------------------------

def test_engine_export_load_zero_compile(tmp_path):
    buckets = (1, 2, 4)
    eng = InferenceEngine(_linear, buckets=buckets, name="cs.a")
    eng.warmup(np.zeros((1, D_IN), "float32"))
    ref = eng.predict(np.ones((3, D_IN), "float32")).asnumpy()
    header = eng.export_artifacts(str(tmp_path))
    assert len(header["entries"]) == len(buckets)
    assert header["extra"]["buckets"] == list(buckets)

    eng2 = InferenceEngine(_linear, buckets=buckets, name="cs.b")
    reset_cache_stats()
    assert eng2.load_artifacts(str(tmp_path)) == len(buckets)
    # every rung serves with zero XLA compiles — the acceptance gate
    for n in (1, 2, 3, 4):
        eng2.predict(np.random.randn(n, D_IN).astype("float32"))
    st = eng2.stats()
    assert st["compiles"] == 0 and st["aot_loads"] == len(buckets)
    assert cache_stats()["misses"] == 0
    np.testing.assert_array_equal(
        eng2.predict(np.ones((3, D_IN), "float32")).asnumpy(), ref)


def test_engine_fingerprint_mismatch_warns_once_and_compiles(tmp_path):
    eng = InferenceEngine(_linear, buckets=(1, 2), name="cs.fp")
    eng.warmup(np.zeros((1, D_IN), "float32"))
    eng.export_artifacts(str(tmp_path))
    # re-stamp the artifact as if exported by another jax on another chip
    path = os.path.join(str(tmp_path), aot.ARTIFACT_NAME)
    header, records = aot.read_artifact(path)
    stale = dict(header["fingerprint"], jax="0.0.0", device_kind="TPU v9")
    aot.write_artifact(path, records, extra=header["extra"], fp=stale)

    eng2 = InferenceEngine(_linear, buckets=(1, 2), name="cs.fp2")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert eng2.load_artifacts(str(tmp_path)) == 0
        eng3 = InferenceEngine(_linear, buckets=(1, 2), name="cs.fp3")
        assert eng3.load_artifacts(str(tmp_path)) == 0   # second refusal
    warned = [x for x in w if issubclass(x.category, RuntimeWarning)
              and "falling back" in str(x.message)]
    assert len(warned) == 1                              # warn-once
    st = pcache.stats()
    assert st["aot_fallbacks"] == 2 and st["aot_loads"] == 0
    # the fallback ledger is a profiler row too
    from mxnet_tpu import profiler
    rows = profiler.get_aggregate_stats()
    assert rows["cachedop.pcache.fallback"]["calls"] == 2
    # and the engine still serves — it just compiles
    eng2.predict(np.ones((2, D_IN), "float32"))
    assert eng2.stats()["compiles"] == 1


def test_engine_ladder_drift_falls_back(tmp_path):
    eng = InferenceEngine(_linear, buckets=(1, 2), name="cs.ld")
    eng.warmup(np.zeros((1, D_IN), "float32"))
    eng.export_artifacts(str(tmp_path))
    eng2 = InferenceEngine(_linear, buckets=(4, 8), name="cs.ld2")
    assert eng2.load_artifacts(str(tmp_path)) == 0
    assert pcache.stats()["aot_fallbacks"] == 1
    eng2.predict(np.ones((3, D_IN), "float32"))          # still serves
    assert eng2.stats()["compiles"] == 1


def test_export_without_compiled_ladder_is_typed(tmp_path):
    eng = InferenceEngine(_linear, buckets=(1, 2), name="cs.empty")
    with pytest.raises(aot.ArtifactError, match="warmup"):
        eng.export_artifacts(str(tmp_path))
    with pytest.raises(ValueError, match="jit=False"):
        InferenceEngine(_linear, buckets=(1,), jit=False,
                        name="cs.nojit").export_artifacts(str(tmp_path))


# ---------------------------------------------------------------------------
# parallel warmup + trace-driven prewarm
# ---------------------------------------------------------------------------

def test_parallel_warmup_compiles_every_rung():
    buckets = (1, 2, 4, 8)
    eng = InferenceEngine(_linear, buckets=buckets, name="cs.par")
    eng.warmup(np.zeros((1, D_IN), "float32"), threads=4)
    st = eng.stats()
    assert st["buckets_seen"] == list(buckets)
    assert st["compiles"] == len(buckets)
    np.testing.assert_allclose(
        eng.predict(np.ones((3, D_IN), "float32")).asnumpy(),
        np.ones((3, D_IN)) @ _W, rtol=1e-5, atol=1e-6)
    assert eng.stats()["compiles"] == len(buckets)       # warm stays warm


def test_warmup_manifest_traffic_frequency_order(tmp_path):
    eng = InferenceEngine(_linear, buckets=(1, 2, 4), name="cs.tm")
    for _ in range(3):
        eng.predict(np.ones((2, D_IN), "float32"))       # bucket 2 x3
    eng.predict(np.ones((1, D_IN), "float32"))           # bucket 1 x1
    manifest = eng.warmup_manifest()
    assert [e["bucket"] for e in manifest["traffic"]] == [2, 1]
    assert [e["count"] for e in manifest["traffic"]] == [3, 1]
    assert manifest["traffic"][0]["shapes"] == [[2, D_IN]]

    mpath = str(tmp_path / "warmup.json")
    eng.write_warmup_manifest(mpath)
    eng2 = InferenceEngine(_linear, buckets=(1, 2, 4), name="cs.tm2")
    eng2.prewarm(manifest=mpath)
    st = eng2.stats()
    assert st["buckets_seen"] == [1, 2]                  # replayed set only
    assert st["compiles"] == 2
    assert st["prewarm"]["status"] == "done"
    assert st["prewarm"]["completed"] == 2


def test_background_prewarm_reports_progress(tmp_path):
    eng = InferenceEngine(_linear, buckets=(1, 2), name="cs.bg")
    eng.predict(np.ones((1, D_IN), "float32"))
    eng.predict(np.ones((2, D_IN), "float32"))
    eng2 = InferenceEngine(_linear, buckets=(1, 2), name="cs.bg2")
    eng2.prewarm(manifest=eng.warmup_manifest(), background=True)
    deadline = time.monotonic() + 60
    while eng2.prewarm_status()["status"] == "running":
        assert time.monotonic() < deadline, "prewarm never finished"
        time.sleep(0.01)
    st = eng2.prewarm_status()
    assert st == {"status": "done", "completed": 2, "total": 2,
                  "error": None}
    assert eng2.stats()["buckets_seen"] == [1, 2]


def test_prewarm_rejects_malformed_manifests():
    eng = InferenceEngine(_linear, buckets=(1, 2), name="cs.bad")
    with pytest.raises(ValueError, match="warmup manifest"):
        eng.prewarm(manifest={"nope": True})
    with pytest.raises(ValueError, match="malformed"):
        eng.prewarm(manifest={"traffic": [{"bucket": 1,
                                           "shapes": "garbage"}]})


def test_prewarm_replays_on_thread_pool():
    buckets = (1, 2, 4, 8)
    eng = InferenceEngine(_linear, buckets=buckets, name="cs.pool")
    for b in buckets:
        eng.predict(np.ones((b, D_IN), "float32"))
    eng2 = InferenceEngine(_linear, buckets=buckets, name="cs.pool2")
    eng2.prewarm(manifest=eng.warmup_manifest(), threads=4)
    st = eng2.stats()
    assert st["prewarm"] == {"status": "done", "completed": len(buckets),
                             "total": len(buckets), "error": None}
    assert st["buckets_seen"] == list(buckets)
    assert st["compiles"] == len(buckets)
    # pooled replay surfaces a rung failure the same way serial does
    eng3 = InferenceEngine(_linear, buckets=(1, 2), name="cs.pool3")
    bad = {"format": 1, "traffic": [
        {"bucket": b, "count": 9 - b, "shapes": [[b, D_IN + 1]],
         "dtypes": ["float32"]} for b in (1, 2)]}
    with pytest.raises(Exception):
        eng3.prewarm(manifest=bad, threads=2)
    assert eng3.prewarm_status()["status"] == "error"


def test_close_stops_background_prewarm():
    def slow(x):
        time.sleep(0.05)
        return _linear(x)

    eng = InferenceEngine(slow, buckets=(1, 2, 4, 8), jit=False,
                          name="cs.stop")
    manifest = {"format": 1, "traffic": [
        {"bucket": b, "count": 9 - b, "shapes": [[b, D_IN]],
         "dtypes": ["float32"]} for b in (1, 2, 4, 8)] * 8}
    eng.prewarm(manifest=manifest, background=True, threads=1)
    eng.close()
    assert eng.prewarm_status()["status"] in ("stopped", "done")
    t = eng._prewarm_thread
    assert t is None or not t.is_alive()


# ---------------------------------------------------------------------------
# fleet manifest executables section + compile-free lane build
# ---------------------------------------------------------------------------

def test_manifest_executables_section_verifies(tmp_path):
    path, _net = _exported_dir(tmp_path)
    manifest = verify_manifest(path)
    exe = manifest["executables"]
    assert exe["artifact"] == aot.ARTIFACT_NAME
    assert exe["count"] == 2 and exe["buckets"] == [1, 2]
    assert exe["warmup"] == aot.WARMUP_NAME
    assert aot.fingerprint_matches(exe["fingerprint"])
    assert exe["sha256"] == manifest["files"][aot.ARTIFACT_NAME]["sha256"]


def test_corrupt_artifact_fails_at_manifest_verify(tmp_path):
    path, _net = _exported_dir(tmp_path)
    apath = os.path.join(path, aot.ARTIFACT_NAME)
    blob = open(apath, "rb").read()
    # flip payload bytes: checksum catches it before any lane builds
    open(apath, "wb").write(blob[:-20] + b"\x00" * 20)
    with pytest.raises(ChecksumMismatch):
        verify_manifest(path)
    # truncation with a "fixed up" manifest: the container's own size
    # arithmetic still refuses, typed, at verify — never at first request
    open(apath, "wb").write(blob[:-20])
    mpath = os.path.join(path, MANIFEST_NAME)
    manifest = json.load(open(mpath))
    digest = hashlib.sha256(blob[:-20]).hexdigest()
    manifest["files"][aot.ARTIFACT_NAME]["sha256"] = digest
    manifest["files"][aot.ARTIFACT_NAME]["bytes"] = len(blob) - 20
    manifest["executables"]["sha256"] = digest
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(aot.ArtifactError, match="truncated|declares"):
        verify_manifest(path)


def test_registry_lane_builds_from_artifacts_compile_free(tmp_path):
    path, net = _exported_dir(tmp_path)
    x = np.random.RandomState(1).randn(2, D_IN).astype("float32")
    ref = net(nd.array(x)).asnumpy()
    reg = ModelRegistry()
    try:
        reset_cache_stats()
        reg.load("m", "v1", path=path, buckets=(1, 2))
        row, mv = reg.predict(x[0], model="m")
        assert cache_stats()["misses"] == 0      # build + serve: no compiles
        assert pcache.stats()["aot_loads"] == 2
        np.testing.assert_allclose(np.asarray(row), ref[0], rtol=1e-5,
                                   atol=1e-6)
        # auto-prewarm replayed the exported warmup.json synchronously
        assert mv.engine.prewarm_status()["status"] == "done"
    finally:
        reg.close()


def test_registry_corrupt_artifact_degrades_to_compiles(tmp_path):
    path, net = _exported_dir(tmp_path)
    apath = os.path.join(path, aot.ARTIFACT_NAME)
    with open(apath, "rb") as f:
        blob = f.read()
    with open(apath, "wb") as f:
        f.write(blob[:len(blob) // 2])
    reg = ModelRegistry()
    try:
        # verify=False skips the manifest gate, so the corruption is only
        # discovered at load_artifacts — the lane must still build
        with warnings.catch_warnings(record=True):
            warnings.simplefilter("always")
            reg.load("m", "v1", path=path, buckets=(1, 2), verify=False)
        assert pcache.stats()["aot_fallbacks"] >= 1
        x = np.random.RandomState(0).randn(1, D_IN).astype("float32")
        ref = net(nd.array(x)).asnumpy()
        row, _mv = reg.predict(x[0], model="m")
        np.testing.assert_allclose(np.asarray(row), ref[0], rtol=1e-5,
                                   atol=1e-6)
        assert cache_stats()["misses"] > 0   # degraded to fresh compiles
    finally:
        reg.close()


def test_model_server_artifacts_dir_serves_compile_free(tmp_path):
    path, _net = _exported_dir(tmp_path)
    eng = InferenceEngine.load(os.path.join(path, "model"), buckets=(1, 2),
                               name="cs.srv")
    reset_cache_stats()
    srv = ModelServer(eng, port=0, artifacts_dir=path)
    srv.start()
    try:
        req = urllib.request.Request(
            srv.url + "/predict",
            data=json.dumps({"data": [0.0] * D_IN}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
            json.loads(resp.read())
        deadline = time.monotonic() + 60
        while eng.prewarm_status()["status"] == "running":
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert eng.prewarm_status()["status"] == "done"
        assert eng.stats()["compiles"] == 0
        assert cache_stats()["misses"] == 0
        # restart health rides /metrics under the coldstart gauge
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=10) as resp:
            metrics = json.loads(resp.read())
        cold = metrics["coldstart"]
        assert cold["pcache"]["aot_loads"] == 2
        assert cold["prewarm"]["status"] == "done"
    finally:
        srv.stop()


def test_model_server_missing_artifacts_degrade_to_compiles(tmp_path):
    eng = InferenceEngine(_linear, buckets=(1,), name="cs.miss")
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        srv = ModelServer(eng, port=0, artifacts_dir=str(tmp_path))
    assert pcache.stats()["aot_fallbacks"] == 1
    srv.start()
    try:
        req = urllib.request.Request(
            srv.url + "/predict",
            data=json.dumps({"data": [0.0] * D_IN}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200                    # compiled, served
    finally:
        srv.stop()


def test_model_server_stop_releases_engine(tmp_path):
    path, _net = _exported_dir(tmp_path)
    eng = InferenceEngine.load(os.path.join(path, "model"), buckets=(1, 2),
                               name="cs.srvstop")
    srv = ModelServer(eng, port=0, artifacts_dir=path)
    srv.start()
    srv.stop()
    # stop() closes the engine: the background prewarm is joined and the
    # ladder's executables are released, not pinned for process lifetime
    t = eng._prewarm_thread
    assert t is None or not t.is_alive()
    assert eng.stats()["size"] == 0


# ---------------------------------------------------------------------------
# persistent compile cache module
# ---------------------------------------------------------------------------

def test_pcache_rows_and_stats_shape():
    from mxnet_tpu import profiler
    rows = profiler.get_aggregate_stats()
    for row in ("cachedop.pcache.hits", "cachedop.pcache.misses",
                "cachedop.pcache.fallback", "cachedop.aot.loads"):
        assert row in rows                   # registered even while off
    st = pcache.stats()
    for key in ("enabled", "dir", "disk_hits", "disk_misses", "requests",
                "ttl_evictions", "aot_loads", "aot_fallbacks"):
        assert key in st


def test_pcache_ttl_sweep(tmp_path):
    old = time.time() - 10 * 86400
    for stem, age in (("aaa", old), ("bbb", None)):
        for suffix in ("-cache", "-atime"):
            p = tmp_path / (stem + suffix)
            p.write_bytes(b"x")
            if age is not None:
                os.utime(p, (age, age))
    assert pcache.sweep_ttl(str(tmp_path), ttl_days=7.0) == 1
    assert not (tmp_path / "aaa-cache").exists()
    assert (tmp_path / "bbb-cache").exists()             # recent survives
    assert pcache.stats()["ttl_evictions"] == 1
    assert pcache.sweep_ttl(str(tmp_path), ttl_days=0) == 0   # 0 = keep


@pytest.fixture
def _pcache_restored():
    """Leave jax's persistent cache as conftest started the suite: off."""
    import jax
    yield
    pcache.init(cache_dir="", force=True)
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("placed_outside", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR", "in_checkout"])
def test_pcache_init_directory(monkeypatch, tmp_path, placed_outside,
                               _pcache_restored):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache is there and
    pcache sets no directory itself (jax owns it); where it is not, the
    cache is the one fixed in-checkout path, never a temp/pid/time name.
    Init creates nothing on disk and leaves jax's on/off flag alone."""
    import jax
    real_update = jax.config.update
    real_update("jax_enable_compilation_cache", True)  # a normal process
    updates = []
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name),
                             real_update(name, value))[1])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if placed_outside:
        outside = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        # what jax does with the variable when it starts
        real_update("jax_compilation_cache_dir", outside)
        assert pcache.init(force=True) == outside
        assert "jax_compilation_cache_dir" not in updates
        assert not os.path.exists(outside)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        expected = os.path.join(repo, ".jax_cache")
        assert pcache.default_dir() == expected
        assert pcache.init(force=True) == expected
        assert jax.config.jax_compilation_cache_dir == expected
    assert "jax_enable_compilation_cache" not in updates
    assert pcache.enabled()
    assert pcache.stats()["dir"] == pcache.cache_dir()
    # started with jax's switch off (the test suite): placed, not enabled
    real_update("jax_enable_compilation_cache", False)
    assert pcache.init(force=True) is None and not pcache.enabled()
    # a test's own directory switches it on; "" takes the directory away
    own = str(tmp_path / "own")
    assert pcache.init(cache_dir=own, force=True) == own
    assert pcache.enabled() and jax.config.jax_enable_compilation_cache
    assert pcache.init(cache_dir="", force=True) is None
    assert jax.config.jax_compilation_cache_dir is None


# ---------------------------------------------------------------------------
# tools/prewarm.py --check: the CI gate
# ---------------------------------------------------------------------------

def _prewarm_tool():
    spec = importlib.util.spec_from_file_location(
        "prewarm_tool", os.path.join(os.path.dirname(__file__), "..",
                                     "tools", "prewarm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_prewarm_check_gate_exit_codes(tmp_path):
    tool = _prewarm_tool()
    # nothing published yet -> 2 (missing)
    empty = tmp_path / "empty"
    empty.mkdir()
    code, report = tool.check(str(empty))
    assert code == 2 and report["status"] == "missing"

    path, _net = _exported_dir(tmp_path)
    code, report = tool.check(path)
    assert code == 0 and report["status"] == "ok"
    assert report["executables"]["count"] == 2

    # stale: artifact stamped by a different jax -> 2 (re-export needed)
    apath = os.path.join(path, aot.ARTIFACT_NAME)
    header, records = aot.read_artifact(apath)
    aot.write_artifact(apath, records, extra=header["extra"],
                       fp=dict(header["fingerprint"], jax="0.0.0"))
    write_manifest(path)
    code, report = tool.check(path)
    assert code == 2 and report["status"] == "stale"
    assert "0.0.0" in report["error"]

    # corrupt: flipped bytes -> 3
    blob = open(apath, "rb").read()
    open(apath, "wb").write(blob[:-10] + b"\x00" * 10)
    code, report = tool.check(path)
    assert code == 3 and report["status"] == "corrupt"
