"""Builder: a latent-attention decoder with held experts
(``mxnet_tpu.models.mla_moe``) behind ``parallel.ShardedTrainer``, trained
as ``configs/bert.py`` trains BERT, with the benchmark's seeded weights in
place of the program's initializer. Next-token loss over every position of
causal sequences whose ids are drawn from the vocabulary slice."""
import numpy as np

from chipbench.configs import mla_moe_ref

_LIVE = []      # the system of this process, for the counter's readers


def model_kwargs(cfg):
    """The program's constructor arguments for a configuration file."""
    return dict(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        hidden_size=cfg["intermediate_size"],
        expert_hidden=cfg["moe_intermediate_size"],
        router_width=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg.get("experts_held_first", 0),
        top_k=cfg["num_experts_per_tok"],
        shared_experts=cfg["n_shared_experts"],
        dense_layers=cfg["first_k_dense_replace"],
        routed_scale=cfg["routed_scaling_factor"],
        normalize=cfg["norm_topk_prob"], rope_theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"], loss_chunk=cfg.get("loss_chunk", 2048))


def build_net(cfg, seed, dtype):
    """The Gluon model holding the seed's weights in ``dtype``; returns
    ``(net, {short name: program's name})``. The program's leaves, without
    the model's own prefix, must be exactly the reference's."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.mla_moe import LatentMoEDecoder
    from mxnet_tpu.ndarray import NDArray
    net = LatentMoEDecoder(**model_kwargs(cfg))
    net.initialize(mx.init.Zero())      # shapes only
    net.cast(dtype)     # the model holds its copy in the storage type too
    weights = mla_moe_ref.make_params(cfg, seed, dtype)
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
        if not mla_moe_ref.takes_gradient(short):
            params[full].grad_req = "null"      # the router's bias
    return net, names


def as_program_batch(tokens):
    """``(tokens, labels)`` as the model takes them: the label of position t
    is token t + 1, and -1 (no target) at the last position of a row."""
    labels = np.concatenate(
        [tokens[:, 1:], np.full((len(tokens), 1), -1, tokens.dtype)], axis=1)
    return (np.asarray(tokens, np.int32), np.asarray(labels, np.int32))


class TrainSystem:
    """The compiled step with its state (``configs/bert.py::TrainSystem``'s
    surface: what ``traffic/train_steps.py`` and the check read)."""

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
            as_program_batch(b["tokens"]) for b in mla_moe_ref.make_batches(
                cfg, self.batch, self.seq, cell["pool"], seed)]
        self._label = np.zeros((self.batch,), np.float32)
        self._norms = jax.jit(lambda leaves: [
            jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves])
        self._diff_norms = jax.jit(lambda new, old: [
            jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(new, old)])
        self._start = None
        self._rows_log = []     # the counter's device arrays, step by step
        _LIVE[:] = [self]

    # ---- the window's own call and feed -----------------------------------
    def _data(self, i):
        mx = self._mx
        host = self.host_batches[i % len(self.host_batches)]
        return tuple(mx.nd.array(a, dtype="int32") for a in host)

    def step(self, i):
        """The host-to-device put a user's loop pays, then
        ``ShardedTrainer.step``; the loss, not waited for. The step's counter
        (rows routed to each held expert, a device array a layer) is kept
        by reference: routing moves as the model trains, and a reader needs
        the rows of the steps it times, not of the last one."""
        loss = self.trainer.step(self._data(i), self._mx.nd.array(self._label))
        self._rows_log.append(
            [layer.routed_rows._data for layer in self.net.expert_layers])
        return loss

    # ---- what the output check reads --------------------------------------
    def _trained(self, arrays):
        return {self._short[p.name]: a
                for p, a in zip(self.trainer._params, arrays)
                if mla_moe_ref.takes_gradient(self._short[p.name])}

    def snapshot_start(self):
        import jax
        self._start = jax.device_get(list(self.trainer._values))

    def _first_moments(self):
        return [s[0] if s else None for s in self.trainer._states]

    def first_gradient_norms(self):
        import jax
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        moments = self._trained(self._first_moments())
        norms = jax.device_get(self._norms(list(moments.values())))
        return {k: float(v) * scale for k, v in zip(moments, norms)}

    def first_gradient(self):
        import jax
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        moments = jax.device_get(self._trained(self._first_moments()))
        return {k: np.asarray(m, np.float32) * scale
                for k, m in moments.items()}

    def change_norms(self):
        import jax
        norms = jax.device_get(self._diff_norms(
            list(self.trainer._values), self._start))
        self._start = None
        return {k: float(v) for k, v in self._trained(norms).items()}

    def routed_rows(self):
        """``[[rows routed to each held expert] per expert layer]`` of the
        last step, fetched from the device now."""
        return [np.asarray(layer.routed_rows.asnumpy(), np.float64).tolist()
                for layer in self.net.expert_layers]

    def routed_rows_by_step(self):
        """``routed_rows`` of every step run so far, fetched now."""
        import jax
        return [[np.asarray(layer, np.float64).tolist() for layer in step]
                for step in jax.device_get(self._rows_log)]

    def compiled_step(self):
        return self.trainer.lower_step(
            self._data(0), self._mx.nd.array(self._label)).compile()

    def close(self):
        self.trainer._values = self.trainer._states = None
        self.trainer = self.net = None
        self._start = None
        self._rows_log = []
        _LIVE[:] = []


def build(cfg, cell, seed, devices):
    return TrainSystem(cfg, cell, seed, devices)


def routed_rows_by_step():
    """The live system's (``TrainSystem.routed_rows_by_step``), or ``None``
    where none is alive."""
    return _LIVE[0].routed_rows_by_step() if _LIVE else None


def reference(cfg, cell, seed, steps, **kw):
    """The plain reference over the same seed (global batch of the cell)."""
    ref_cell = dict(cell, batch=cell["batch"] * cell.get("dp", 1))
    return mla_moe_ref.run_steps(cfg, ref_cell, seed, steps, **kw)
