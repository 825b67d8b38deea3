"""Device time by the scopes the program wrote, from what a traced run
already holds: the compiled step's text (``obs["step_text"]``) and the
trace's seconds by instruction name (``obs["trace"]["by_name"]``).

The program enters ``jax.named_scope`` around its layers (every Gluon block
under its own name, ``attention`` around the attention op whatever
implements it, ``optimizer`` around the update), and JAX writes the stack
into each instruction's ``metadata={op_name="jit(step)/jvp(net0)/.../
attention/mul"}``. A transform wraps the outermost scope only
(``jvp(...)``, ``transpose(jvp(...))``), and the backward rule of a
``custom_vjp`` keeps the scope of its call site, so a scope is matched as a
whole path component, bare or wrapped. A fusion carries ONE ``op_name``,
the compiler's choice (its root's; for a multi-output fusion around a
matmul, the matmul's), and belongs to that scope whole: a weight-gradient
matmul fused with its Adam update is the block's, not the optimizer's, so
:func:`held_pct` also says how much time goes to instructions that HOLD a
scope somewhere in their body. Where XLA merged instructions it joins their
``op_name`` with ``;`` and the instruction belongs to the first of
``attention``, ``optimizer``, a block that any of them names. The
instruction names (``fusion.12``) are the ones ``trace_reduce.short_name``
keeps from the ``XLA Ops`` events.
"""
import functools
import re

# the two scopes the program writes itself, beside each block's name
# (``mxnet_tpu.base.PROGRAM_SCOPES``; a block named so enters ``<name>_``)
ATTENTION, OPTIMIZER = "attention", "optimizer"
BLOCKS, UNSCOPED = "blocks", "unscoped"

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*\bop_name="([^"]*)"')
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_SCALAR = re.compile(r" = \w+\[\]")
_NAME = re.compile(r"^[\w.\-]+$")
# path components JAX writes for its own structure: nobody's layer
_STRUCTURAL = re.compile(
    r"^(?:while|body|cond|scan|checkpoint|remat|shard_map|closed_call|"
    r"custom_jvp_call|custom_vjp_call|branch_\d+_fun)$")


def op_names(text):
    """``{instruction name: op_name}`` over every instruction of a compiled
    program's text that carries one."""
    found = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found.setdefault(m.group(1), m.group(2))
    return found


def _components(op_name):
    """Split at the ``/`` outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def scope_path(op_name):
    """The scopes an ``op_name`` lies under, outermost first: the components
    between the program (``jit(step)``) and the primitive, transforms
    unwrapped, cut at the first inner ``jit(...)`` (a function JAX named, not
    a scope), without JAX's structural names and without what is no name
    (``jnp.einsum`` enters a scope of its subscripts). An ``op_name`` that
    does not start at a program (XLA made the instruction) has none."""
    parts = _components(op_name)
    if len(parts) < 3 or not re.match(r"^p?jit\(", parts[0]):
        return []
    path = []
    for part in parts[1:-1]:
        while True:
            m = _TRANSFORM.match(part)
            if not m:
                break
            part = m.group(1)
        if re.match(r"^p?jit\(", part):
            break
        if _NAME.match(part) and not _STRUCTURAL.match(part):
            path.append(part)
    return path


def layer_of(op_name):
    """One of ``attention``, ``optimizer``, ``blocks`` (some block's scope
    and neither of the two) or ``unscoped``."""
    paths = [scope_path(one) for one in (op_name or "").split(";")]
    for layer in (ATTENTION, OPTIMIZER):
        if any(layer in path for path in paths):
            return layer
    return BLOCKS if any(paths) else UNSCOPED


@functools.lru_cache(maxsize=1)
def layers_of(text):
    """``{instruction name: layer}`` of a compiled program's text (kept for
    the one text a run reads: several readers ask, and the text is long)."""
    return {name: layer_of(op) for name, op in op_names(text).items()}


@functools.lru_cache(maxsize=1)
def layers_held(text):
    """``{instruction name: set of layers}`` for the instructions that call
    a computation (fusions): the layers of the instructions in its body,
    scalars left out (a step count raised to a power does no work)."""
    inside, calls, current = {}, {}, None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            current = inside.setdefault(header.group(1), set())
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        if current is not None and not _SCALAR.search(line):
            current.add(layer_of(m.group(2)))
        called = _CALLS.search(line)
        if called:
            calls[m.group(1)] = called.group(1)
    return {name: inside.get(body, set()) for name, body in calls.items()}


def seconds_by_layer(obs):
    """``{layer: device seconds}`` of the traced window over the four layers
    of :func:`layer_of`, or ``None`` where there is no trace, no step text,
    or a program that wrote neither of its own two scopes anywhere (a
    commit from before the scopes): then nothing can be put down to a
    layer."""
    trace, text = obs.get("trace"), obs.get("step_text")
    if trace is None or not text:
        return None
    layers = layers_of(text)
    if not {ATTENTION, OPTIMIZER} & set(layers.values()):
        return None
    total = {ATTENTION: 0.0, OPTIMIZER: 0.0, BLOCKS: 0.0, UNSCOPED: 0.0}
    for name, seconds in trace["by_name"].items():
        total[layers.get(name, UNSCOPED)] += seconds
    return total


def share_pct(obs, layer):
    """Device time under ``layer`` as a share of the window's busy time."""
    if obs["kind"] != "train":
        return None
    by_layer = seconds_by_layer(obs)
    if by_layer is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * by_layer[layer] / obs["trace"]["busy_s"]


def held_pct(obs, layer):
    """Device time of the instructions that are ``layer``'s or hold one of
    its operations in their body, as a share of the window's busy time: an
    upper bound on the layer, where :func:`share_pct` is a lower one."""
    if obs["kind"] != "train" or seconds_by_layer(obs) is None \
            or not obs["trace"]["busy_s"]:
        return None
    layers, held = layers_of(obs["step_text"]), layers_held(obs["step_text"])
    seconds = sum(s for name, s in obs["trace"]["by_name"].items()
                  if layers.get(name) == layer or layer in held.get(name, ()))
    return 100.0 * seconds / obs["trace"]["busy_s"]


def steps_traced(obs, layer):
    """How many steps of the compiled program the trace holds, counted from
    the events of ``layer``'s instructions: each runs once a step, so the
    events over the distinct names seen is the steps (a fraction where the
    window cut one)."""
    layers = layers_of(obs["step_text"])
    counts = {}
    for name, _, _ in obs["trace"]["events"]:
        if layers.get(name) == layer:
            counts[name] = counts.get(name, 0) + 1
    return sum(counts.values()) / len(counts) if counts else 0.0
