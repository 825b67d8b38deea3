"""Data-fed ResNet-50 training benchmark: the native IO pipeline
(lib/libmxtpu.so: RecordIO scan -> JPEG/raw decode -> augment -> uint8
batches, double-buffered) feeding the compiled training step on the chip.

This is the apples-to-apples counterpart of the reference's headline
298.51 img/s (V100, train_imagenet.py through its C++ ImageRecordIter,
reference docs perf.md:252) — unlike bench.py, whose batches are
generated in-graph.

Pipeline design (TPU-native):
- host ships raw uint8 NHWC (4x fewer bytes over the host->device link
  than f32); normalize + layout + bf16 cast run INSIDE the compiled step
  (ShardedTrainer preprocess), fused by XLA;
- batches transfer as individual ~4.8MB puts, stacked on device and
  dispatched as one step_many chunk; a feeder thread stages chunk N+1
  while the device runs chunk N.

The benchmark decomposes throughput into its four independent rates:
  io       host decode+augment rate (pump drain, no device)
  wire     host->device transfer rate, idle link
  wire_c   host->device transfer rate WHILE compute is in flight
  compute  the same training program with batches generated in-graph
and reports fed-rate plus pipeline efficiency = fed / min(io, wire_c,
compute) — how close the overlap gets to the binding constraint.

Env knobs: DF_BATCH (32), DF_CHUNK (steps per dispatch, 16), DF_CHUNKS
(measured chunks, 6), DF_N_IMG (records in the generated .rec, 1024),
DF_FORMAT (raw|jpg; jpg decode is host-core-bound: ~430 img/s/core
measured — this box has 1 core, a real TPU-VM host has 100+).
"""
import io as pyio
import json
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

BASELINE_IMG_S = 298.51  # reference perf.md:252 (V100, fp32, batch 32)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(metric, value, unit, **kw):
    print(json.dumps(dict(metric=metric, value=round(value, 2), unit=unit,
                          **kw)), flush=True)


def make_rec(path, n, size, fmt):
    from mxnet_tpu.recordio import MXRecordIO, IRHeader, pack, pack_img
    rng = np.random.RandomState(0)
    rec = MXRecordIO(path, "w")
    # a handful of distinct images referenced round-robin keeps .rec build
    # time negligible while still exercising full decode per record
    base = [(rng.rand(size, size, 3) * 255).astype(np.uint8)
            for _ in range(32)]
    if fmt == "jpg":
        from PIL import Image
        payloads = []
        for im in base:
            b = pyio.BytesIO()
            Image.fromarray(im).save(b, format="JPEG", quality=90)
            payloads.append(b.getvalue())
        for i in range(n):
            rec.write(pack(IRHeader(0, float(i % 1000), i, 0),
                           payloads[i % 32]))
    else:
        for i in range(n):
            rec.write(pack_img(IRHeader(0, float(i % 1000), i, 0),
                               base[i % 32], img_fmt=".raw"))
    rec.close()


def main():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel, _native
    from mxnet_tpu.gluon.model_zoo import vision

    batch = int(os.environ.get("DF_BATCH", "32"))
    chunk = int(os.environ.get("DF_CHUNK", "16"))
    n_chunks = int(os.environ.get("DF_CHUNKS", "6"))
    n_img = int(os.environ.get("DF_N_IMG", "1024"))
    fmt = os.environ.get("DF_FORMAT", "raw")
    image = 224
    src_size = 256

    rec_path = "/tmp/bench_datafed_%s_%d.rec" % (fmt, n_img)
    if not os.path.exists(rec_path):
        log("building %s (%d records of %d^2 %s)..."
            % (rec_path, n_img, src_size, fmt))
        make_rec(rec_path, n_img, src_size, fmt)

    log("devices:", jax.devices())
    d = jax.devices()[0]
    shape = (3, image, image)

    # --- phase 1: pure IO (pump drain, no device) ---
    pump = _native.Pump(rec_path, batch, shape, rand_crop=True,
                        rand_mirror=True, shuffle=True, u8_output=True,
                        depth=4)
    drain_n = min(pump.batches_per_epoch, 40)
    for _ in range(4):
        pump.next()  # warm
    t0 = time.time()
    got = 0
    while got < drain_n:
        if pump.next() is not None:
            got += 1
    io_rate = drain_n * batch / (time.time() - t0)
    log("pure IO (decode+augment, %s): %.0f img/s" % (fmt, io_rate))
    emit("io_pump_%s_img_per_sec" % fmt, io_rate, "img/s")

    def drain():
        while True:
            item = pump.next()
            if item is not None:
                return item

    # --- phase 2: wire, idle link ---
    xs_host = [drain() for _ in range(16)]
    jax.block_until_ready(jax.device_put(xs_host[0][0], d))
    t0 = time.time()
    for x, _ in xs_host:
        jax.block_until_ready(jax.device_put(x, d))
    wire_rate = 16 * batch / (time.time() - t0)
    log("wire (uint8 b%d puts, idle): %.0f img/s" % (batch, wire_rate))
    emit("wire_idle_img_per_sec", wire_rate, "img/s")

    # --- model + trainer with in-step preprocess ---
    mean = jnp.array([123.68, 116.779, 103.939], jnp.float32)
    std = jnp.array([58.393, 57.12, 57.375], jnp.float32)

    def preprocess(x):
        x = (x.astype(jnp.float32) - mean) / std
        return x.transpose(0, 3, 1, 2).astype(jnp.bfloat16)

    mx.random.seed(0)
    net = vision.resnet50_v1()
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1,) + shape))
    net.cast("bfloat16")
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh(dp=1), preprocess=preprocess)

    # --- phase 3: pure compute (same program, in-graph uint8 batches) ---
    steps = chunk * n_chunks
    log("compiling bench_span (%d steps)..." % steps)
    l = trainer.bench_span(steps, (batch, image, image, 3), 1000,
                           dtype="bfloat16")
    l.asnumpy()
    t0 = time.time()
    l = trainer.bench_span(steps, (batch, image, image, 3), 1000,
                           dtype="bfloat16")
    l.asnumpy()
    compute_rate = steps * batch / (time.time() - t0)
    log("pure compute (in-graph uint8 + preprocess): %.0f img/s"
        % compute_rate)
    emit("compute_u8span_img_per_sec", compute_rate, "img/s")

    # --- phase 4: wire under compute contention ---
    staged = [0]

    def contender():
        t_end = time.time() + 6.0
        while time.time() < t_end:
            x, _ = xs_host[staged[0] % 16]
            jax.block_until_ready(jax.device_put(x, d))
            staged[0] += 1

    th = threading.Thread(target=contender)
    th.start()
    t0 = time.time()
    while th.is_alive():
        trainer.bench_span(chunk, (batch, image, image, 3), 1000,
                           dtype="bfloat16").asnumpy()
    th.join()
    wire_c_rate = staged[0] * batch / 6.0
    log("wire under compute contention: %.0f img/s" % wire_c_rate)
    emit("wire_contended_img_per_sec", wire_c_rate, "img/s")

    # --- phase 5: data-fed (feeder thread stages device chunks) ---
    stack = jax.jit(lambda *parts: jnp.stack(parts))

    def stage_chunk():
        xs, ys = [], []
        for _ in range(chunk):
            x, y = drain()
            xs.append(jax.device_put(x, d))
            ys.append(y)
        return stack(*xs), np.stack(ys)

    log("compiling step_many (chunk=%d)..." % chunk)
    xc, yc = stage_chunk()
    trainer.step_many(xc, yc)  # compile + warm

    q = queue.Queue(maxsize=2)
    stop = [False]

    def feeder():
        while not stop[0]:
            item = stage_chunk()
            while not stop[0]:
                try:
                    q.put(item, timeout=0.5)
                    break
                except queue.Full:
                    pass

    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    loss = None
    t0 = time.time()
    for _ in range(n_chunks):
        xc, yc = q.get()
        loss = trainer.step_many(xc, yc)  # async dispatch
    loss.asnumpy()
    dt = time.time() - t0
    stop[0] = True
    th.join()          # drain the feeder fully before later phases
    while not q.empty():
        q.get()        # release staged device chunks
    fed_rate = n_chunks * chunk * batch / dt
    bound = min(io_rate, wire_c_rate, compute_rate)
    log("data-fed training: %.0f img/s (binding constraint %.0f img/s -> "
        "pipeline efficiency %.0f%%)"
        % (fed_rate, bound, 100 * fed_rate / bound))
    emit("resnet50_train_datafed_%s_img_per_sec_b%d" % (fmt, batch),
         fed_rate, "img/s",
         vs_baseline=round(fed_rate / BASELINE_IMG_S, 3),
         pipeline_efficiency_vs_bound=round(fed_rate / bound, 3),
         bound="io" if bound == io_rate else
               ("wire_contended" if bound == wire_c_rate else "compute"))

    # --- phase 6: gap-scheduled alternation (round 4) ---
    # For a link where transfers cannot ride alongside in-flight compute
    # (phase 4 measures whether they can): stage the next chunk's device
    # puts in the GAP between dispatches — host decode still overlaps
    # compute (it never touches the device), only the puts serialize:
    #   per chunk: T_wire(idle rate) + T_compute, vs the naive feeder's
    #   T_wire(contended rate).
    host_q = queue.Queue(maxsize=2 * chunk)
    stop2 = [False]

    def host_feeder():  # pure host work: safe to overlap compute
        while not stop2[0]:
            item = drain()
            while not stop2[0]:
                try:
                    host_q.put(item, timeout=0.5)
                    break
                except queue.Full:
                    pass

    th2 = threading.Thread(target=host_feeder, daemon=True)
    th2.start()

    def put_chunk():
        xs, ys = [], []
        for _ in range(chunk):
            x, y = host_q.get()
            xs.append(jax.device_put(x, d))
            ys.append(y)
        return stack(*xs), np.stack(ys)

    xc, yc = put_chunk()
    trainer.step_many(xc, yc).asnumpy()  # warm
    t0 = time.time()
    xc, yc = put_chunk()   # chunk 0's puts are part of the measured cost
    for i in range(n_chunks):
        loss = trainer.step_many(xc, yc)   # async dispatch
        if i + 1 < n_chunks:
            # drain the device FIRST so the puts see an idle channel
            loss.asnumpy()
            xc, yc = put_chunk()
    loss.asnumpy()
    dt = time.time() - t0
    stop2[0] = True
    fed_gap = n_chunks * chunk * batch / dt
    # serial-channel model: 1/rate = 1/wire_idle + 1/compute
    model_rate = 1.0 / (1.0 / wire_rate + 1.0 / compute_rate)
    log("data-fed (gap-scheduled): %.0f img/s (serial-channel model "
        "%.0f img/s, %.0f%% of compute)"
        % (fed_gap, model_rate, 100 * fed_gap / compute_rate))
    emit("resnet50_train_datafed_gapsched_%s_img_per_sec_b%d"
         % (fmt, batch), fed_gap, "img/s",
         vs_baseline=round(fed_gap / BASELINE_IMG_S, 3),
         fraction_of_compute=round(fed_gap / compute_rate, 3),
         serial_channel_model_img_per_sec=round(model_rate, 1))

    # --- phase 7: pre-staged device pool ---
    # Stage a data pool BEFORE the first training dispatch and train from
    # device-resident chunks: the upper bound a feeder can reach, with no
    # transfer in flight while the chip computes. A 16 GB HBM holds ~90k
    # uint8 224^2 images alongside ResNet-50 training state — the
    # small-dataset epoch-caching strategy.
    # (Pool chunks were NOT donated by step_many: reusable every epoch.)
    if os.environ.get("DF_POOL", "1") != "0":
        n_pool = min(n_chunks, 8)
        pool = []
        t0 = time.time()
        for _ in range(n_pool):
            xs = []
            for _ in range(chunk):
                x, _y = host_q.get() if not host_q.empty() else drain()
                xs.append(jax.device_put(x, d))
            pool.append(jax.block_until_ready(stack(*xs)))
        stage_t = time.time() - t0
        log("NOTE: pool staged AFTER first dispatch here (degraded puts, "
            "%.1fs); in a fresh process staging runs at the idle wire "
            "rate — see PERF.md" % stage_t)
        yd = jax.device_put(jnp.asarray(yc), d)  # labels device-resident
        loss = None
        t0 = time.time()
        for c in range(n_pool):
            loss = trainer.step_many(pool[c], yd)
        loss.asnumpy()
        dt = time.time() - t0
        pool_rate = n_pool * chunk * batch / dt
        log("data-fed (device pool): %.0f img/s (%.0f%% of compute)"
            % (pool_rate, 100 * pool_rate / compute_rate))
        emit("resnet50_train_datafed_devicepool_%s_img_per_sec_b%d"
             % (fmt, batch), pool_rate, "img/s",
             vs_baseline=round(pool_rate / BASELINE_IMG_S, 3),
             fraction_of_compute=round(pool_rate / compute_rate, 3))


if __name__ == "__main__":
    main()
