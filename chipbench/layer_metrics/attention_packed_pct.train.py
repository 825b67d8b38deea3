"""Share of the attention calls traced into the program that the dispatcher
sent to the flash kernels on the PACKED QKV projection (no split, no 4-D
view, one packed gradient), from the program's own
``ops.nn.attention_dispatch_stats()``: packed over packed + flash + xla,
counted where the dispatcher decides, once a trace. ``None`` where the
program has no such counter, or traced no attention."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import nn
    stats = getattr(nn, "attention_dispatch_stats", None)
    if stats is None:
        return None
    counts = stats()
    total = sum(counts.values())
    return 100.0 * counts["packed"] / total if total else None
