"""``cachedop.compile`` spans plus persistent-cache requests inside the
window of a training cell: nothing may compile there (reads 0)."""
from chipbench.readers import compiles_in_window


def read(obs):
    return compiles_in_window(obs, "train")
