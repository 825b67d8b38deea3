"""Share of its roofline the causal flash forward kernel reaches in
prefill: the least time for the attention of the prefills traced (every
``generation.prefill`` span that lies whole inside the traced window, at its
rung, float32 operands) over the device time of the custom-call events that
ran inside those spans."""
from chipbench import flops


def read(obs):
    trace = obs["trace"]
    if obs["kind"] != "serve" or trace is None or obs["peaks"] is None:
        return None
    shift = trace["to_monotonic"]
    lo, hi = (t + shift for t in trace["window"])
    spans = [(s, e, a["rung"]) for n, s, e, a in obs["spans"]
             if n == "generation.prefill" and s >= lo and e <= hi]
    kernels = [(s + shift, e + shift) for n, s, e in trace["events"]
               if n in trace["custom_calls"]]
    cfg, peaks = obs["cfg"], obs["peaks"]
    least = took = 0.0
    for s0, s1, rung in spans:
        inside = [e - s for s, e in kernels if s >= s0 and e <= s1]
        if not inside:
            continue
        took += sum(inside)
        least += cfg["num_hidden_layers"] * flops.roofline_seconds(
            *flops.flash_forward_cost(1, rung, cfg, 4, True), peaks)
    return 100.0 * least / took if took else None
