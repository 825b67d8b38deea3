"""Share of the latent-attention backwards traced into the program that took
the ONE kernel ``flash_latent_bwd`` (dq summed in VMEM along the key axis, one
pass over each live tile's scores) and not the ``flash_latent_dq`` +
``flash_latent_dkv`` pair, from the program's own
``ops.pallas_kernels.latent_backward_stats()``: fused over fused + split,
counted where the backward decides, once a trace. ``None`` where the program
has no such counter, or traced no latent backward."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import pallas_kernels
    stats = getattr(pallas_kernels, "latent_backward_stats", None)
    if stats is None:
        return None
    counts = stats()
    total = sum(counts.values())
    return 100.0 * counts["fused"] / total if total else None
