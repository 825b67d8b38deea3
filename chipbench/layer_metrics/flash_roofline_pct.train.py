"""Share of their roofline the Mosaic attention kernels reach in training:
the least time the chip could take for the attention of the steps traced
(forward and backward of every layer, from ``flops.flash_*_cost``; the larger
of operations over peak FLOP/s and bytes over peak bytes/s) over the device
time of the trace's custom-call events. The steps traced are the kernel
events counted, over the calls the compiled step holds."""
from chipbench import flops


def read(obs):
    trace = obs["trace"]
    if obs["kind"] != "train" or trace is None or obs["peaks"] is None:
        return None
    kernels = [(e - s) for n, s, e in trace["events"]
               if n in trace["custom_calls"]]
    calls_per_step = (obs.get("step_text") or "").count("tpu_custom_call")
    if not kernels or not calls_per_step:
        return None
    cfg, peaks = obs["cfg"], obs["peaks"]
    rows = obs["batch"] // obs["chips"]
    least = sum(flops.roofline_seconds(*cost(rows, obs["seq"], cfg, 2, False),
                                       peaks)
                for cost in (flops.flash_forward_cost,
                             flops.flash_backward_cost))
    steps = len(kernels) / calls_per_step
    return 100.0 * least * cfg["num_hidden_layers"] * steps / sum(kernels)
