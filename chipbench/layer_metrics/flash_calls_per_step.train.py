"""Pallas (Mosaic) custom calls in the compiled training step: which path
the attention dispatcher took (12 layers x forward, dq, dkdv = 36)."""


def read(obs):
    text = obs.get("step_text")
    if not text:
        return None
    return float(text.count("tpu_custom_call"))
