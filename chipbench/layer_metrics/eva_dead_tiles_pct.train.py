"""Of the key tiles the EVA kernels' grids step through (the forward, the
backward and dsum of every backward traced), the share that holds no live
pair, from
the counter the kernels' planner exports
(``ops.pallas_kernels.eva_tile_stats()``, counted once a trace). A dead tile
is skipped and fetches nothing, but costs its grid step; 0 where the grids
cover live tiles only. ``None`` where the program has no such counter, or
traced no EVA backward."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.ops import pallas_kernels
    stats = getattr(pallas_kernels, "eva_tile_stats", None)
    counts = stats() if stats else None
    if not counts or not counts["stepped"]:
        return None
    return 100.0 * (counts["stepped"] - counts["live"]) / counts["stepped"]
