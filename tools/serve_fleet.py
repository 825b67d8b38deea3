#!/usr/bin/env python
"""Horizontal serving supervisor: gateway + N supervised replicas.

The serving-side sibling of ``tools/launch.py --supervise`` (ROADMAP
item 5): spawn N ``ModelServer`` replica *processes*, put the
load-aware :class:`mxnet_tpu.serving.Gateway` in front, and keep the
fleet alive —

- a crashed replica is respawned with exponential backoff and rejoins
  health-gated (it takes no traffic until ``/healthz`` says ok; with
  published AOT artifacts that is the zero-compile restart path);
- ``SIGHUP`` triggers a drain-aware rolling restart of the whole fleet
  (zero dropped requests — the deploy primitive);
- ``--autoscale MIN:MAX`` turns on the queue-depth / p99-SLO autoscaler,
  growing and shrinking the replica set through the same spawn/drain
  machinery;
- ``--event-log`` records every transition (spawn, up, drain, restart,
  eject, scale) as JSON lines — the recovery-time source for
  ``benchmark/gateway_bench.py``;
- ``--telemetry-port`` serves ONE merged rank-labelled ``/metrics.prom``
  for the whole fleet via ``tools/telemetry_agg.py``'s parallel scrape,
  re-pointed automatically as replicas come and go.

Replicas default to a built-in demo model (a small MLP — enough to
exercise the full path); real deployments pass ``--worker-cmd`` with a
``{port}`` placeholder, e.g.::

    python tools/serve_fleet.py --replicas 4 --port 8080 \\
        --worker-cmd 'python my_server.py --port {port}'

The worker contract is just: serve ``ModelServer``'s HTTP surface on
``{port}`` (``/healthz``, ``/metrics``, ``/drain``) and drain on
SIGTERM (``ModelServer.install_drain_handler``). Chaos drills ride the
environment: ``MXNET_CHAOS_SPEC='serving.execute:host_loss:at=40'``
in one replica's env makes it die mid-request under load — the gateway
absorbs it (see docs/resilience.md).
"""
from __future__ import annotations

import argparse
import itertools
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# signal-safe flags the handlers flip; the main loop does the real work
_FLAGS = {"stop": False, "rolling_restart": False}


def _free_port(host="127.0.0.1"):
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ProcessBackend:
    """Replica lifecycle over real OS processes — the production shape
    (one PJRT client per process). Implements the gateway's backend
    duck-type: ``spawn() -> (url, meta)``, ``restart(replica)``,
    ``stop(replica)``.

    A chip belongs to one process, so each replica is handed its own:
    the lowest chip index no live replica of this backend holds, through
    libtpu's own variables (the child then sees exactly that chip; a CPU
    child ignores them). This process never touches jax — a parent that
    had would hold every chip itself. An index past the host's last chip
    makes that child fail at start-up, which is the truth: there is no
    chip for it.

    Each worker runs in its own process group so a kill takes its whole
    tree, ``launch.py`` style. Restarts land on a FRESH port (no
    TIME_WAIT races); the gateway learns the new URL from
    ``restart``'s return value."""

    def __init__(self, worker_cmd=None, host="127.0.0.1",
                 stop_grace_s=15.0, extra_env=None):
        self.worker_cmd = worker_cmd  # string with {port}, or None = demo
        self.host = host
        self.stop_grace_s = float(stop_grace_s)
        self.extra_env = dict(extra_env or {})
        self._chips = set()           # chip indices live replicas hold
        self._chips_lock = threading.Lock()

    def _claim_chip(self):
        with self._chips_lock:
            chip = next(i for i in itertools.count()
                        if i not in self._chips)
            self._chips.add(chip)
        return chip

    def _command(self, port):
        if self.worker_cmd:
            return shlex.split(self.worker_cmd.format(port=port))
        return [sys.executable, os.path.abspath(__file__),
                "--worker", "--worker-port", str(port)]

    def spawn(self, port=None, env=None):
        port = port or _free_port(self.host)
        chip = self._claim_chip()
        penv = dict(os.environ)
        penv.update(TPU_VISIBLE_CHIPS=str(chip),
                    TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                    TPU_PROCESS_BOUNDS="1,1,1")
        penv.update(self.extra_env)
        penv.update(env or {})
        proc = subprocess.Popen(self._command(port), env=penv,
                                start_new_session=True)
        url = "http://%s:%d" % (self.host, port)
        return url, {"proc": proc, "port": port, "chip": chip}

    def _terminate(self, meta):
        proc = (meta or {}).get("proc")
        if proc is None:
            return
        try:
            self._stop_process(proc)
        finally:
            with self._chips_lock:
                self._chips.discard(meta.get("chip"))

    def _stop_process(self, proc):
        if proc.poll() is not None:
            return
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                proc.terminate()
            except (ProcessLookupError, OSError):
                pass
        try:
            # SIGTERM → ModelServer.install_drain_handler bounded drain
            # → clean exit; SIGKILL only past the grace window
            proc.wait(self.stop_grace_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError, OSError):
                proc.kill()
            proc.wait(5.0)

    def restart(self, replica):
        self._terminate(replica.meta)
        url, meta = self.spawn()
        replica.meta = meta
        return url

    def stop(self, replica):
        self._terminate(replica.meta)


# ---------------------------------------------------------------------------
# worker mode (demo model)
# ---------------------------------------------------------------------------

def run_worker(args):
    """One replica process: demo MLP behind a full ``ModelServer``,
    draining (not dropping) on SIGTERM."""
    import numpy as np

    from mxnet_tpu import nd
    from mxnet_tpu.serving import ModelServer

    d_in, d_hid = args.demo_dim, args.demo_dim * 2
    rng = np.random.default_rng(0)
    w1 = nd.array(rng.standard_normal((d_in, d_hid)).astype("float32"))
    w2 = nd.array(rng.standard_normal((d_hid, d_in)).astype("float32"))

    def model(x):
        return nd.dot(nd.relu(nd.dot(x, w1)), w2)

    srv = ModelServer(model, host=args.host, port=args.worker_port,
                      buckets=(1, 2, 4, 8), max_latency_ms=2.0,
                      artifacts_dir=args.artifacts_dir or None)
    # warm the whole bucket ladder BEFORE the listener answers: the
    # gateway's health-gated admission then means "compiled and ready",
    # not "about to stall every early request on XLA" (with
    # --artifacts-dir the AOT install already made these free)
    srv.engine.warmup(np.zeros((1, d_in), "float32"))
    # supervisor kills are SIGTERM-first: always drain, then exit 0 so
    # the monitor loop can tell a clean stop from a crash
    srv.install_drain_handler(on_stopped=lambda: os._exit(0))
    sys.stderr.write("serve_fleet worker: serving on %s (pid %d)\n"
                     % (srv.url, os.getpid()))
    sys.stderr.flush()
    srv.serve()
    return 0


# ---------------------------------------------------------------------------
# supervisor mode
# ---------------------------------------------------------------------------

def _retarget_telemetry(agg, gateway):
    agg.set_targets({r.id: r.url for r in gateway.replicas()})


def run_supervisor(args):
    from mxnet_tpu import config as _config
    from mxnet_tpu.serving import Autoscaler, Gateway

    backend = ProcessBackend(worker_cmd=args.worker_cmd, host=args.host)
    gateway = Gateway(backend=backend, host=args.host, port=args.port,
                      event_log=args.event_log or None)

    agg = agg_server = None
    if args.telemetry_port:
        import telemetry_agg  # sibling module, pure stdlib
        agg = telemetry_agg.Aggregator({})
        agg_server = telemetry_agg.AggServer(
            agg, host=args.host, port=args.telemetry_port)

    restarts = {}  # replica id -> consecutive respawn count

    def _add_one():
        url, meta = backend.spawn()
        rep = gateway.add_replica(url, meta=meta)
        gateway.log_event("replica_spawned", replica=rep.id, url=url,
                          pid=meta["proc"].pid)
        return rep

    for _ in range(args.replicas):
        _add_one()
    if agg is not None:
        _retarget_telemetry(agg, gateway)

    autoscaler = None
    if args.autoscale:
        lo, _, hi = args.autoscale.partition(":")
        autoscaler = Autoscaler(gateway, backend=backend,
                                min_replicas=int(lo),
                                max_replicas=int(hi or lo),
                                interval_s=args.autoscale_interval_s)
        autoscaler.start()

    signal.signal(signal.SIGTERM,
                  lambda *_: _FLAGS.__setitem__("stop", True))
    signal.signal(signal.SIGINT,
                  lambda *_: _FLAGS.__setitem__("stop", True))
    signal.signal(signal.SIGHUP,
                  lambda *_: _FLAGS.__setitem__("rolling_restart", True))

    gateway.start()
    sys.stderr.write(
        "serve_fleet: gateway on %s over %d replica(s)%s%s\n"
        % (gateway.url, args.replicas,
           " (autoscale %s)" % args.autoscale if args.autoscale else "",
           " telemetry :%d" % args.telemetry_port
           if args.telemetry_port else ""))
    sys.stderr.flush()

    backoff_s = _config.get("MXNET_ELASTIC_BACKOFF_MS") / 1e3
    max_restarts = _config.get("MXNET_ELASTIC_MAX_RESTARTS")
    try:
        while not _FLAGS["stop"]:
            if _FLAGS["rolling_restart"]:
                _FLAGS["rolling_restart"] = False
                gateway.log_event("rolling_restart_requested")
                gateway.rolling_restart(backend)
                if agg is not None:
                    _retarget_telemetry(agg, gateway)
            # crash watch: a dead process whose replica is not mid-drain
            # is respawned with backoff (launch.py --supervise policy)
            for rep in gateway.replicas():
                proc = (rep.meta or {}).get("proc")
                if proc is None:
                    continue
                if proc.poll() is None:  # alive
                    if rep.health == "ok":
                        restarts.pop(rep.id, None)  # streak broken
                    continue
                if rep.state == "draining":
                    continue  # being restarted/stopped on purpose
                rc = proc.returncode
                n = restarts.get(rep.id, 0) + 1
                gateway.log_event("replica_exited", replica=rep.id,
                                  rc=rc, respawn=n)
                gateway.remove_replica(rep.id)
                backend.stop(rep)   # already dead: hands its chip back
                if max_restarts and n > max_restarts:
                    gateway.log_event("replica_evicted", replica=rep.id,
                                      rc=rc)
                    continue
                time.sleep(min(backoff_s * (2 ** (n - 1)), 30.0))
                new = _add_one()
                restarts[new.id] = n
                if agg is not None:
                    _retarget_telemetry(agg, gateway)
            time.sleep(args.monitor_interval_s)
    finally:
        gateway.log_event("supervisor_stopping")
        if autoscaler is not None:
            autoscaler.close()
        for rep in gateway.replicas():
            gateway.mark_draining(rep.id)
        for rep in gateway.replicas():
            gateway.wait_drained(rep.id, timeout_s=5.0)
            backend.stop(rep)
        gateway.close()
        if agg_server is not None:
            agg_server.close()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="fault-tolerant load-aware gateway over N supervised "
                    "ModelServer replicas")
    ap.add_argument("--replicas", type=int, default=2,
                    help="initial replica count (default 2)")
    ap.add_argument("--port", type=int, default=8080,
                    help="gateway listen port (default 8080)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--worker-cmd", default=None,
                    help="replica command template with a {port} "
                         "placeholder (default: built-in demo worker)")
    ap.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                    help="enable the SLO/queue autoscaler between MIN "
                         "and MAX replicas")
    ap.add_argument("--autoscale-interval-s", type=float, default=1.0)
    ap.add_argument("--monitor-interval-s", type=float, default=0.5)
    ap.add_argument("--event-log", default=None,
                    help="JSON-lines lifecycle transition log")
    ap.add_argument("--telemetry-port", type=int, default=0,
                    help="serve a merged rank-labelled /metrics.prom for "
                         "the whole fleet on this port (telemetry_agg)")
    # worker mode (internal: what --worker-cmd defaults to)
    ap.add_argument("--worker", action="store_true",
                    help="run ONE demo replica process (internal)")
    ap.add_argument("--worker-port", type=int, default=0)
    ap.add_argument("--demo-dim", type=int, default=64)
    ap.add_argument("--artifacts-dir", default=None,
                    help="AOT artifacts dir for zero-compile worker "
                         "restarts (demo worker only)")
    args = ap.parse_args(argv)
    if args.worker:
        return run_worker(args)
    return run_supervisor(args)


if __name__ == "__main__":
    sys.exit(main())
