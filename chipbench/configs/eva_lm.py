"""Builder: a byte-level decoder with EVA attention
(``mxnet_tpu.models.eva_lm``) behind ``parallel.ShardedTrainer``, trained as
``configs/mla_moe.py`` trains its decoder, with the benchmark's seeded
weights in place of the program's initializer. The loss is over every
position and every prediction head that has a byte to predict."""
import numpy as np

from chipbench.configs import eva_lm_ref, mla_moe


def model_kwargs(cfg):
    """The program's constructor arguments for a configuration file."""
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        hidden_size=cfg["intermediate_size"], window=cfg["window_size"],
        chunk=cfg["chunk_size"], pred_heads=cfg["num_pred_heads"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        loss_chunk=cfg.get("loss_chunk", 2048),
        recompute=cfg.get("recompute", False))


def build_net(cfg, seed, dtype):
    """The Gluon model holding the seed's weights in ``dtype``; returns
    ``(net, {short name: program's name})``. The program's leaves, without
    the model's own prefix, must be exactly the reference's."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.eva_lm import EvaDecoder
    from mxnet_tpu.ndarray import NDArray
    net = EvaDecoder(**model_kwargs(cfg))
    net.initialize(mx.init.Zero())      # shapes only
    net.cast(dtype)     # the model holds its copy in the storage type too
    weights = eva_lm_ref.make_params(cfg, seed, dtype)
    params = net.collect_params()
    names = {k[len(net.prefix):]: k for k in params.keys()}
    if set(names) != set(weights):
        raise RuntimeError("program and reference disagree on the leaves: %s"
                           % sorted(set(names) ^ set(weights)))
    for short, full in names.items():
        if tuple(params[full].shape) != tuple(weights[short].shape):
            raise RuntimeError("shape of %s: program %s, reference %s" % (
                short, params[full].shape, weights[short].shape))
        params[full].set_data(NDArray(weights[short]))
    return net, names


def as_program_batch(tokens, heads):
    """``(tokens, labels)`` as the model takes them."""
    return (np.asarray(tokens, np.int32),
            np.asarray(eva_lm_ref.make_labels(tokens, heads), np.int32))


class TrainSystem(mla_moe.TrainSystem):
    """``configs/mla_moe.py::TrainSystem``'s surface on this model: every
    leaf is trained and no step keeps a counter."""

    def __init__(self, cfg, cell, seed, devices):
        import jax
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        self._mx = mx
        self.cfg, self.cell = cfg, cell
        dp = cell.get("dp", 1)
        self.batch = cell["batch"] * dp
        self.seq = cell["seq"]
        self.picked = self.seq - 1              # targets of the first head
        self.tokens_per_step = self.batch * self.seq
        self.net, names = build_net(cfg, seed, cfg["param_dtype"])
        opt = cfg["optimizer"]
        self.trainer = parallel.ShardedTrainer(
            self.net, lambda out, _label: out, opt["name"],
            {"learning_rate": opt["learning_rate"], "beta1": opt["beta1"],
             "beta2": opt["beta2"], "epsilon": opt["epsilon"]},
            mesh=parallel.make_mesh(dp=dp, devices=list(devices[:dp])),
            dtype=cfg["param_dtype"])
        self._short = {full: short for short, full in names.items()}
        self.host_batches = [
            as_program_batch(b["tokens"], cfg["num_pred_heads"])
            for b in eva_lm_ref.make_batches(cfg, self.batch, self.seq,
                                             cell["pool"], seed)]
        self._label = np.zeros((self.batch,), np.float32)
        self._norms = jax.jit(lambda leaves: [
            jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves])
        self._diff_norms = jax.jit(lambda new, old: [
            jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(new, old)])
        self._start = None

    def step(self, i):
        return self.trainer.step(self._data(i),
                                 self._mx.nd.array(self._label))

    def _trained(self, arrays):
        return {self._short[p.name]: a
                for p, a in zip(self.trainer._params, arrays)}

    def close(self):
        self.trainer._values = self.trainer._states = None
        self.trainer = self.net = None
        self._start = None


def build(cfg, cell, seed, devices):
    return TrainSystem(cfg, cell, seed, devices)


def reference(cfg, cell, seed, steps, **kw):
    """The plain reference over the same seed (global batch of the cell)."""
    ref_cell = dict(cell, batch=cell["batch"] * cell.get("dp", 1))
    return eva_lm_ref.run_steps(cfg, ref_cell, seed, steps, **kw)
