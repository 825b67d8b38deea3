"""Share of the held experts' sums over a token's rows traced into the
program that took the grouped product over blocks of tokens (megablox's
``tgmm``) and not ``jax.ops.segment_sum``, from the program's own
``parallel.moe.combine_stats()``: grouped over grouped + xla, counted where
the sum decides, once a trace. 100 intended on one chip. ``None`` where the
program has no such counter, or traced no such sum."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.parallel import moe
    stats = getattr(moe, "combine_stats", None)
    if stats is None:
        return None
    counts = stats()
    total = sum(counts.values())
    return 100.0 * counts["grouped"] / total if total else None
