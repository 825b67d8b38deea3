"""Share of the state-space scans traced into the program that the op sent
to the chunked-scan kernels, from the program's own
``ops.nn.ssm_scan_stats()``: kernel over both paths, counted where
``ops.nn.ssm_scan`` decides, once a trace. 100 is the intended
reading; 0 means every scan fell back to XLA. ``None`` where the program has
no such counter, or traced no scan."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import nn
    stats = getattr(nn, "ssm_scan_stats", None)
    counts = stats() if stats else {}
    total = sum(counts.values())
    return 100.0 * counts["kernel"] / total if total else None
