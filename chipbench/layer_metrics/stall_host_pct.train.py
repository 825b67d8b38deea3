"""Of the stalled intervals' excess over the median, the share NOT spent
waiting for a value longer than the median interval does (``ndarray.wait``):
100 means the host was late (placing, launching, ``trainer.step``'s own time,
or no span at all), 0 that it sat waiting, so the device or the runtime was
late. Where ``stall_s.train`` is 0 it is the same share of the LONGEST
interval's excess: which side that window's jitter came from, of
milliseconds; read it as a stall's only beside a ``stall_s.train`` above 0."""
from chipbench.host_timeline import stall


def read(obs):
    return stall(obs, "stall_host_pct")
