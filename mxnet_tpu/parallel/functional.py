"""Functionalize Gluon blocks and optimizers for pjit'd SPMD training.

This is the bridge between MXNet's stateful semantics (mutable Parameters,
stateful Optimizer.update — reference `python/mxnet/gluon/trainer.py` +
`src/kvstore/`) and XLA's functional SPMD world: a Block becomes a pure
function of (rng, params, inputs); an Optimizer becomes (init_state,
update) pure functions reusing the exact jitted kernels from
mxnet_tpu.optimizer (numerical parity with the eager Trainer path).
"""
from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp

from .. import _tape
from .. import random as _random
from ..optimizer import optimizer as _opt

__all__ = ["functionalize", "functional_optimizer", "shard_params"]


def _raw(fn):
    """Un-jitted view of a kernel (avoids nested-donation warnings)."""
    return getattr(fn, "__wrapped__", fn)


def functionalize(block, train=True):
    """Return (pure_fn, params). ``pure_fn(rng_key, param_vals, *inputs)``
    → (outputs_tuple, aux_vals_tuple); aux_vals align with ``aux_handles``
    attribute set on the function (BatchNorm moving stats etc.)."""
    from ..context import current_context
    from ..gluon.parameter import swapped_in
    from ..ndarray.ndarray import NDArray
    params = list(block.collect_params().values())

    def pure(rng_key, param_vals, *input_vals):
        # the caller's mesh places this program, and ``param_vals`` stand in
        # for every copy the block holds. A traced array claims the CURRENT
        # context, so where the block's own copy lives elsewhere (on the
        # host, say, to leave the chip to the trainer's) the block is called
        # under the context of ITS copy (the inputs labelled with it), and
        # every ``p.data(x.context)`` finds the value swapped in. Otherwise
        # nothing is labelled and nothing entered.
        held = params[0].list_ctx() if params else []
        away = held[0] if held and current_context() not in held else None
        nds = [NDArray(v, ctx=away) for v in input_vals]
        _random.push_trace_key(rng_key)
        prev_rec = _tape.set_recording(False)
        prev_train = _tape.set_training(train)
        sink = _tape.push_aux_sink()
        try:
            with swapped_in(params, param_vals), \
                    away or contextlib.nullcontext():
                out = block(*nds)
        finally:
            _tape.pop_aux_sink()
            _tape.set_training(prev_train)
            _tape.set_recording(prev_rec)
            _random.pop_trace_key()
        outs = out if isinstance(out, (list, tuple)) else (out,)
        pure.aux_handles = [h for h, _ in sink]
        return tuple(o._data for o in outs), tuple(v for _, v in sink)

    pure.aux_handles = []
    return pure, params


def functional_optimizer(name, **hyper):
    """(init_state, update) pure pair over one tensor; reuses the jitted
    kernels so results match the eager Optimizer exactly."""
    name = name.lower()
    lr = hyper.get("learning_rate", 0.01)
    wd = hyper.get("wd", 0.0)
    mom = hyper.get("momentum", 0.0)
    rescale = hyper.get("rescale_grad", 1.0)
    clip = hyper.get("clip_gradient", None)
    clip = _opt._INF if clip is None else clip
    b1 = hyper.get("beta1", 0.9)
    b2 = hyper.get("beta2", 0.999)
    eps = hyper.get("epsilon", 1e-8)

    if name == "sgd":
        if mom:
            def init(w):
                return (jnp.zeros_like(w),)

            def update(w, g, state, t, lr_t):
                w2, m2 = _raw(_opt._sgd_mom)(w, state[0], g, lr_t, wd,
                                                  mom, rescale, clip)
                return w2, (m2,)
        else:
            def init(w):
                return ()

            def update(w, g, state, t, lr_t):
                return _raw(_opt._sgd)(w, g, lr_t, wd, rescale, clip), ()
        return init, update
    if name == "nag":
        def init(w):
            return (jnp.zeros_like(w),)

        def update(w, g, state, t, lr_t):
            w2, m2 = _raw(_opt._nag_mom)(w, state[0], g, lr_t, wd, mom,
                                               rescale, clip)
            return w2, (m2,)
        return init, update
    if name == "adam":
        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(w, g, state, t, lr_t):
            w2, m2, v2 = _raw(_opt._adam)(w, state[0], state[1], g,
                                                lr_t, wd, b1, b2, eps,
                                                rescale, clip, t)
            return w2, (m2, v2)
        return init, update
    if name == "lamb":
        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(w, g, state, t, lr_t):
            w2, m2, v2 = _raw(_opt._lamb)(
                w, state[0], state[1], g, lr_t, wd, b1, b2, eps, t,
                0.0, _opt._INF, 1.0, rescale, clip)
            return w2, (m2, v2)
        return init, update
    raise ValueError("functional optimizer %r not supported (use sgd, nag, "
                     "adam, lamb)" % name)


def shard_params(params, mesh, rules=None):
    """Compute a NamedSharding per parameter from (regex → PartitionSpec)
    rules; unmatched params are replicated. This is the pjit version of the
    reference's `group2ctx` model-parallel placement
    (`graph_executor.cc:1956`)."""
    from jax.sharding import NamedSharding, PartitionSpec
    shardings = []
    rules = rules or []
    for p in params:
        spec = PartitionSpec()
        for pat, s in rules:
            if re.search(pat, p.name):
                spec = s
                break
        shardings.append(NamedSharding(mesh, spec))
    return shardings
