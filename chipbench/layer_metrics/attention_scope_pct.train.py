"""Share of the device's busy time spent under the program's ``attention``
scope (``ops/nn.py::dot_product_attention``: kernels or XLA softmax, the
mask reduction, the layout copies in and out, forward and backward)."""
from chipbench import scopes


def read(obs):
    return scopes.share_pct(obs, scopes.ATTENTION)
