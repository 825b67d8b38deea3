"""Share of the head-fused attention backwards traced into the program that
took the ONE fused kernel (dq, dk and dv from a single pass over the scores,
where one key block spans the sequence) and not the dq-then-dkdv pair, from
the program's own ``ops.pallas_kernels.flash_backward_stats()``: fused over
fused + split, counted where the backward decides, once a trace. ``None``
where the program has no such counter, or traced no such backward."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import pallas_kernels
    stats = getattr(pallas_kernels, "flash_backward_stats", None)
    if stats is None:
        return None
    counts = stats()
    total = sum(counts.values())
    return 100.0 * counts["fused"] / total if total else None
