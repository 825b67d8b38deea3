"""Share of the mixers' causal convolutions traced into the program that the
op sent to the convolution kernels, from the program's own
``ops.nn.ssm_conv_stats()``: kernel over both paths, counted where
``ops.nn.causal_conv1d`` decides, once a trace. 100 is the intended
reading; 0 means every convolution fell back to XLA. ``None`` where the
program has no such counter, or traced no convolution."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import nn
    stats = getattr(nn, "ssm_conv_stats", None)
    counts = stats() if stats else {}
    total = sum(counts.values())
    return 100.0 * counts["kernel"] / total if total else None
