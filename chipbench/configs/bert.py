"""Builder: BERT pretraining behind ``parallel.ShardedTrainer``, as
``chip_smoke.py::bert_trainer`` builds it (copied, not imported), with the
benchmark's seeded weights in place of the program's initializer."""
import numpy as np

from chipbench.configs import bert_ref
from chipbench.weights import put_into


class TrainSystem:
    """The compiled step with its state: what set-up drives through the
    checked first steps and the window then keeps driving."""

    def __init__(self, cfg, cell, seed, devices):
        import jax
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.gluon.block import HybridBlock
        from mxnet_tpu.models.bert import BERTModel, BERTPretrainingLoss
        from mxnet_tpu.ndarray import NDArray

        class PretrainStep(HybridBlock):
            """Whole pretraining loss inside the block: the trainer sees a
            scalar. Gather-first decode on the masked slots."""

            def __init__(self, bert, **kw):
                super().__init__(**kw)
                with self.name_scope():
                    self.bert = bert
                self.loss = BERTPretrainingLoss(picked=True)

            def hybrid_forward(self, F, tokens, segments, valid_len,
                               positions, labels, weights, nsp_labels):
                _, _, mlm_logits, nsp_logits = self.bert(
                    tokens, segments, valid_len, positions)
                return self.loss(mlm_logits, nsp_logits, labels, positions,
                                 weights, nsp_labels)

        self._mx = mx
        self.cfg, self.cell = cfg, cell
        dp = cell.get("dp", 1)
        self.batch = cell["batch"] * dp         # global batch
        self.seq, self.picked = cell["seq"], cell["picked"]
        self.tokens_per_step = self.batch * self.seq
        net = BERTModel(
            vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            hidden_size=cfg["intermediate_size"],
            max_length=cfg["max_position_embeddings"],
            num_segments=cfg["type_vocab_size"],
            dropout=cfg["hidden_dropout_prob"])
        # shapes only: the values come from the seed, made on the device
        net.initialize(mx.init.Zero())
        names = put_into(net, bert_ref.param_spec(cfg), seed, "float32",
                         NDArray)
        opt = cfg["optimizer"]
        self.trainer = parallel.ShardedTrainer(
            PretrainStep(net), lambda out, _label: out, opt["name"],
            {"learning_rate": opt["learning_rate"], "beta1": opt["beta1"],
             "beta2": opt["beta2"], "epsilon": opt["epsilon"]},
            mesh=parallel.make_mesh(dp=dp, devices=list(devices[:dp])),
            dtype=cfg["param_dtype"])
        self._short = {full: short for short, full in names.items()}
        ref_batches = bert_ref.make_batches(cfg, self.batch, self.seq,
                                            self.picked, cell["pool"], seed)
        self.host_batches = [self._as_program_batch(b) for b in ref_batches]
        self._label = np.zeros((self.batch,), np.float32)
        self._norms = jax.jit(lambda leaves: [
            jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves])
        self._diff_norms = jax.jit(lambda new, old: [
            jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(new, old)])
        self._start = None

    @staticmethod
    def _as_program_batch(b):
        """The model takes every input as float32 (it casts ids itself)."""
        return tuple(np.asarray(b[k], np.float32) for k in (
            "tokens", "segments", "valid_len", "positions", "labels",
            "weights", "nsp"))

    # ---- the window's own call and feed -----------------------------------
    def step(self, i):
        """Step on host batch ``i`` of the pool: the host-to-device put a
        user's loop pays, then ``ShardedTrainer.step``. Returns the loss
        (not waited for)."""
        mx = self._mx
        host = self.host_batches[i % len(self.host_batches)]
        data = tuple(mx.nd.array(a) for a in host)
        return self.trainer.step(data, mx.nd.array(self._label))

    # ---- what the output check reads --------------------------------------
    def _leaves(self, arrays):
        return {self._short[p.name]: a
                for p, a in zip(self.trainer._params, arrays)}

    def snapshot_start(self):
        """Keep a HOST copy of the parameters before the first step: what
        only the check holds stays off the chip while a step runs, so
        ``memory_peak_bytes`` is the step's own."""
        import jax
        self._start = jax.device_get(list(self.trainer._values))

    def first_gradient_norms(self):
        """Per-leaf norm of the first gradient as the optimizer got it, from
        Adam's first moment after one step: m1 = (1 - beta1) * g1."""
        import jax
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        norms = jax.device_get(self._norms(
            [s[0] for s in self.trainer._states]))
        return {k: float(v) * scale for k, v in self._leaves(norms).items()}

    def first_gradient(self):
        """The first gradient itself, leaf by leaf on the HOST in float32
        (Adam's first moment over 1 - beta1): it outlives :meth:`close` and
        takes no device memory through the window."""
        import jax
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        moments = jax.device_get([s[0] for s in self.trainer._states])
        return {k: np.asarray(m, np.float32) * scale
                for k, m in self._leaves(moments).items()}

    def change_norms(self):
        """Per-leaf norm of the parameters' change since the snapshot."""
        import jax
        norms = jax.device_get(self._diff_norms(
            list(self.trainer._values), self._start))
        self._start = None
        return {k: float(v) for k, v in self._leaves(norms).items()}

    def compiled_step(self):
        """The step program as compiled for this batch (from the cache)."""
        mx = self._mx
        data = tuple(mx.nd.array(a) for a in self.host_batches[0])
        return self.trainer.lower_step(
            data, mx.nd.array(self._label)).compile()

    def close(self):
        """Free the program's device state before the reference runs."""
        self.trainer._values = self.trainer._states = None
        self.trainer = None
        self._start = None


def build(cfg, cell, seed, devices):
    return TrainSystem(cfg, cell, seed, devices)


def reference(cfg, cell, seed, steps, **kw):
    """The plain reference over the same seed (global batch of the cell)."""
    ref_cell = dict(cell, batch=cell["batch"] * cell.get("dp", 1))
    return bert_ref.run_steps(cfg, ref_cell, seed, steps, **kw)
