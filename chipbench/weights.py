"""Weights from ``--seed``, made on the device in one jitted call.

A configuration's reference module lists its leaves as
``{name: (shape, kind)}`` with ``kind`` one of ``normal`` (N(0, 0.02), the
initializer range both published configurations state), ``ones`` and
``zeros``. The benchmark hands the same arrays to the program (through its
builder) and, made anew after the window, to the reference: neither takes
anything the other has made.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def seed_key(seed):
    """A threefry key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec, dtype):
    out = {}
    for i, (name, shape, kind) in enumerate(spec):
        if kind == "normal":
            leaf = INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "ones":
            leaf = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            raise ValueError("unknown leaf kind %r for %s" % (kind, name))
        out[name] = leaf.astype(dtype)
    return out


def make_weights(spec, seed, dtype="float32"):
    """``{name: array}`` on the default device for ``spec`` =
    ``{name: (shape, kind)}``; the same seed gives the same arrays."""
    flat = tuple((name, tuple(shape), kind)
                 for name, (shape, kind) in spec.items())
    return _make(seed_key(seed), flat, dtype)


def put_into(net, spec, seed, dtype, wrap):
    """Give the Gluon block ``net`` (initialised, shapes known) the seed's
    values for ``spec``; ``wrap`` makes the program's array type from a
    device array. The program's leaves, without the block's own prefix, must
    be exactly the reference's. Returns ``{short name: program's name}``."""
    weights = make_weights(spec, seed, dtype)
    params = net.collect_params()
    names = {k[len(net.prefix):]: k for k in params.keys()}
    if set(names) != set(spec):
        raise RuntimeError("program and reference disagree on the leaves: %s"
                           % sorted(set(names) ^ set(spec)))
    for short, full in names.items():
        if tuple(params[full].shape) != tuple(spec[short][0]):
            raise RuntimeError("shape of %s: program %s, reference %s" % (
                short, params[full].shape, spec[short][0]))
        params[full].set_data(wrap(weights[short]))
    return names
