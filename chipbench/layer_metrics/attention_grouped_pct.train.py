"""Share of the attention calls traced into the program that the dispatcher
sent to the grouped-query flash kernels, from the program's own
``ops.nn.attention_dispatch_stats()``: grouped over all paths, counted where
the dispatcher decides, once a trace. 100 is the intended reading; 0 means
the dispatcher fell back to XLA. ``None`` where the program has no such
counter or path, or traced no attention."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import nn
    stats = getattr(nn, "attention_dispatch_stats", None)
    counts = stats() if stats else {}
    total = sum(counts.values())
    return 100.0 * counts["grouped"] / total if total and "grouped" in counts \
        else None
