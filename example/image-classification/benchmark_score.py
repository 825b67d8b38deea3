"""Inference throughput benchmark across the model zoo.

CLI parity with the reference `example/image-classification/benchmark_score.py`
(the script behind BASELINE.md's inference tables, reference perf.md:194).
TPU-native: each model's forward is functionalized once, jitted as a single
XLA program, and timed with a device->host sync bounding each measurement.

Usage:
  python benchmark_score.py [--model resnet-50] [--batch-size 1,32,64]
                            [--dtype bfloat16] [--image-shape 3,224,224]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.parallel.functional import functionalize

# reference benchmark_score.py model list (its get_symbol zoo), mapped to
# the Gluon model zoo constructors
MODELS = {
    "alexnet": vision.alexnet,
    "vgg-16": lambda: vision.get_vgg(16),
    "inception-v3": vision.inception_v3,
    "resnet-50": vision.resnet50_v1,
    "resnet-152": vision.resnet152_v1,
    "squeezenet": vision.squeezenet1_0,
    "mobilenet": vision.mobilenet1_0,
    "mobilenet-v2": vision.mobilenet_v2_1_0,
    "densenet-121": vision.densenet121,
}


def score(model_name, batch, image_shape, dtype, repeat=3, iters=None):
    import jax
    import jax.numpy as jnp

    mx.random.seed(0)
    np.random.seed(0)
    net = MODELS[model_name]()
    net.initialize(mx.init.Xavier())
    c, h, w = image_shape
    net(mx.nd.zeros((1, c, h, w)))
    if dtype == "bfloat16":
        net.cast("bfloat16")
    elif dtype == "int8":
        # real int8 path: conv/dense swapped for int8 blocks with ranges
        # calibrated on one batch (docs/quantization.md)
        from mxnet_tpu.contrib.quantization import quantize_net
        calib = mx.nd.array(np.random.rand(batch, c, h, w)
                            .astype("float32"))
        net = quantize_net(net, calib_data=[calib], calib_mode="naive")
    pure, params = functionalize(net, train=False)
    pvals = [p.data()._data for p in params]
    key = jax.random.PRNGKey(0)

    # image sizes below the model's design resolution can pool down to an
    # EMPTY output tensor, which XLA then rightly dead-codes to nothing —
    # refuse to report a meaningless number
    (probe,), _ = pure(key, pvals, jnp.zeros(
        (1, c, h, w), jnp.bfloat16 if dtype == "bfloat16" else jnp.float32))
    if probe.size == 0:
        raise ValueError(
            "%s produces an empty output at %dx%d — use a larger "
            "--image-shape" % (model_name, h, w))

    if iters is None:
        # long spans amortize the per-call dispatch overhead
        on_tpu = any(d.platform != "cpu" for d in jax.devices())
        iters = 400 if on_tpu else 10

    @jax.jit
    def many(x):
        def body(carry, _):
            (out,), _aux = pure(key, pvals, carry)
            # feed the output back in so XLA cannot dead-code or overlap
            # iterations. NOTE: `0 * mean` or a denormal multiplier is NOT
            # safe — XLA folds provably-non-NaN chains away (verified: int8
            # nets got fully eliminated). 1e-6 keeps a real serial data
            # dependency; the ~1e-6 input drift is irrelevant for timing.
            return carry + 1e-6 * jnp.mean(out).astype(carry.dtype), ()
        final, _ = jax.lax.scan(body, x, None, length=iters)
        return jnp.mean(final)  # scalar D2H sync, not the full batch

    x = jnp.asarray(np.random.rand(batch, c, h, w).astype("float32"))
    if dtype == "bfloat16":
        x = x.astype(jnp.bfloat16)
    np.asarray(many(x))  # compile + warm
    best = 0.0
    for _ in range(repeat):
        t0 = time.time()
        np.asarray(many(x))  # D2H sync bounds the span
        dt = time.time() - t0
        best = max(best, batch * iters / dt)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="all",
                    help="model name or 'all' (%s)" % ",".join(MODELS))
    ap.add_argument("--batch-size", default="1,32",
                    help="comma-separated batch sizes")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--iters", type=int, default=None,
                    help="forwards per compiled span (default: 400 on "
                         "TPU, 10 on CPU)")
    args = ap.parse_args()

    shape = tuple(int(v) for v in args.image_shape.split(","))
    names = list(MODELS) if args.model == "all" else args.model.split(",")
    for name in names:
        for b in (int(v) for v in args.batch_size.split(",")):
            try:
                img_s = score(name, b, shape, args.dtype, iters=args.iters)
            except ValueError as e:  # e.g. empty output at this resolution
                print("model: %s, dtype: %s, batch: %d, SKIPPED (%s)"
                      % (name, args.dtype, b, e), flush=True)
                continue
            print("model: %s, dtype: %s, batch: %d, images/sec: %.2f"
                  % (name, args.dtype, b, img_s), flush=True)


if __name__ == "__main__":
    main()
