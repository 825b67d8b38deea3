"""Builder: a hybrid state-space / attention decoder
(``mxnet_tpu.models.hybrid_ssm``) behind ``parallel.ShardedTrainer``, trained
as ``configs/mla_moe.py`` trains its decoder, with the benchmark's seeded
weights in place of the program's initializer. Next-token loss over every
position but a row's last; the head is the embedding."""
import numpy as np

from chipbench.configs import eva_lm, hybrid_ssm_ref, mla_moe


def model_kwargs(cfg):
    """The program's constructor arguments for a configuration file."""
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        layer_types=tuple(hybrid_ssm_ref.layer_types(cfg)),
        hidden_size=cfg["shared_intermediate_size"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], conv_taps=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        logits_scaling=cfg["logits_scaling"], eps=cfg["rms_norm_eps"],
        loss_chunk=cfg.get("loss_chunk", 2048),
        recompute=cfg.get("recompute", False))


def build_net(cfg, seed, dtype):
    """The Gluon model holding the seed's weights in ``dtype``; returns
    ``(net, {short name: program's name})``. The program's leaves, without
    the model's own prefix, must be exactly the reference's."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.hybrid_ssm import HybridDecoder
    from mxnet_tpu.ndarray import NDArray
    net = HybridDecoder(**model_kwargs(cfg))
    # shapes only, and on the HOST: the trainer places its own copy on the
    # chip, and the Gluon model's copy with its gradient buffers (4 B a
    # parameter) would not fit beside it there
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    net.cast(dtype)     # the model holds its copy in the storage type too
    weights = hybrid_ssm_ref.make_params(cfg, seed, dtype)
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


class TrainSystem(eva_lm.TrainSystem):
    """``configs/eva_lm.py::TrainSystem`` (every leaf trained, no counter a
    step) on this model and its batches."""

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
        self.picked = self.seq - 1              # targets a row
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
            mla_moe.as_program_batch(b["tokens"])
            for b in hybrid_ssm_ref.make_batches(cfg, self.batch, self.seq,
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


def build(cfg, cell, seed, devices):
    return TrainSystem(cfg, cell, seed, devices)


def reference(cfg, cell, seed, steps, **kw):
    """The plain reference over the same seed (global batch of the cell)."""
    ref_cell = dict(cell, batch=cell["batch"] * cell.get("dp", 1))
    return hybrid_ssm_ref.run_steps(cfg, ref_cell, seed, steps, **kw)
