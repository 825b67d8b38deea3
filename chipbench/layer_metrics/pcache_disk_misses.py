"""Programs the persistent compile cache did not hold, counted at the start
of the window: what set-up had to compile."""


def read(obs):
    return float(obs["counters"]["pcache"]["disk_misses"])
