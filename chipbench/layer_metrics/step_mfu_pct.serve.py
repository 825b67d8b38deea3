"""The serving work's share of the chip's peak: forward operations the
model needs (``flops.lm_prefill_flops`` / ``lm_decode_flops``) for the
prompts whose first token and the generated tokens that arrived inside the
window, over window x peak FLOP/s."""
from chipbench import flops


def read(obs):
    if obs["kind"] != "serve" or obs["peaks"] is None:
        return None
    cfg, t0, t1 = obs["cfg"], obs["t0"], obs["t1"]
    total = 0.0
    for r in obs["requests"]:
        times = r["token_times"]
        if times and t0 <= times[0] < t1:
            total += flops.lm_prefill_flops(cfg, r["prompt_len"])
        # token i (i >= 1) came from a decode step over prompt + i - 1 cached
        total += sum(flops.lm_decode_flops(cfg, r["prompt_len"] + i - 1)
                     for i, t in enumerate(times) if i and t0 <= t < t1)
    return 100.0 * total / (obs["window_s"] * obs["peaks"]["flops_per_s"])
