"""Share of the device's busy time in operations that carry no scope of the
program (no block's, not ``attention``, not ``optimizer``): what the names
cannot see."""
from chipbench import scopes


def read(obs):
    return scopes.share_pct(obs, scopes.UNSCOPED)
