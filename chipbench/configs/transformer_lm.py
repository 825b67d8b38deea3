"""Builder: ``models.transformer.TransformerLM`` behind ``DecodeEngine`` ->
``GenerationScheduler``, every engine and scheduler setting as shipped but
those the configuration file states, with the benchmark's seeded weights."""
from chipbench.configs import transformer_lm_ref
from chipbench.weights import make_weights, put_into


class ServeSystem:
    """The scheduler the window drives: ``submit`` is the call the
    ``/generate`` handler makes; clients consume ``request.tokens()``."""

    def __init__(self, cfg, cell, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu.models.transformer import TransformerLM
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving.generation import (DecodeEngine,
                                                  GenerationScheduler)
        self.cfg = cfg
        serving = cfg["serving"]
        lm = TransformerLM(
            cfg["vocab_size"], units=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            hidden_size=cfg["intermediate_size"], max_len=cfg["n_positions"])
        lm.initialize(mx.init.Zero())
        self._lm, self._wrap = lm, NDArray
        self.set_weights(seed)
        self.engine = DecodeEngine(
            lm, num_slots=serving["num_slots"], max_seq=serving["max_seq"],
            ladder=tuple(serving["ladder"]), dtype=serving["arena_dtype"],
            # None: the engine's own default (on); False: off
            prefix_cache=None if serving["prefix_cache"] else False)
        self.scheduler = GenerationScheduler(self.engine)

    def set_weights(self, seed):
        """The seed's weights into the model: the engine's programs take
        them as arguments, so nothing compiles again."""
        put_into(self._lm, transformer_lm_ref.param_spec(self.cfg), seed,
                 self.cfg["param_dtype"], self._wrap)

    def submit(self, prompt, max_new_tokens):
        """One greedy generation; returns the program's request object."""
        return self.scheduler.submit(prompt, max_new_tokens=max_new_tokens,
                                     temperature=0.0)

    def counters(self):
        return {"scheduler": self.scheduler.stats()}

    def close(self):
        """Stop the worker and free the arenas before the reference runs."""
        self.scheduler.close(drain=False, timeout=30.0)
        self.engine.close()
        self.scheduler = self.engine = None


def build(cfg, cell, seed, devices):
    return ServeSystem(cfg, cell, seed, devices)


def reference_weights(cfg, seed):
    """The seed's weights made anew, in float32, for the reference."""
    return make_weights(transformer_lm_ref.param_spec(cfg), seed, "float32")


served_logits = transformer_lm_ref.served_logits
