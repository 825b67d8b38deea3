#!/usr/bin/env python
"""Is a cell's step still the same program? The hash of its lowered step at a
small size, for the TPU platform, without the chip (PR 29's recipe): build the
cell's ``TrainSystem`` on the CPU with the platform seam patched, lower the
trainer's jitted step for ``tpu``, replace every Mosaic kernel body (base64
MLIR bytecode, which carries source lines) by the hash of its assembly without
debug info, hash the text. Beside that hash it prints the "frame" (the text
with every body left out) and each kernel name's bodies hashed, so that a
change to one kernel shows as that name alone. Run this ONE script from the
root of each tree
(``git archive <parent>`` into a git-ignored directory, and the change) and
compare; the hash depends on the size chosen, so only two runs of one script
compare. Keys after the cell override its workload file:

    JAX_PLATFORMS=cpu JAX_ENABLE_COMPILATION_CACHE=false \
        python <repo>/tools/lowered_step_hash.py bert_base_train_s512 batch=2
    ... kimi_vl_a3b_train_s8192 batch=1 | evabyte_train_s32768 seq=4096 |
        granite_4_0_h_micro_train_s32768 seq=4096

A cell takes a minute or two and up to 10 GB of host memory: one at a time.
"""
import base64, hashlib, json, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import importlib
import jax
from chipbench import run as bench_run

cell_name = sys.argv[1]
over = {k: int(v) for k, v in (a.split("=") for a in sys.argv[2:])}
entry, cfg, cell = bench_run.load_cell(cell_name)
cell.update(over)
if "seq" in over and cell["picked"] >= cell["seq"]:
    cell["picked"] = cell["seq"] - 1    # a target at every position but the last
from mxnet_tpu.ops import nn as nn_ops
nn_ops._on_accelerator = lambda: True
import mxnet_tpu as mx
builder = importlib.import_module(cfg["builder"])
system = builder.build(cfg, cell, 7, jax.devices()[:1])
tr = system.trainer
tr._build_step()
host = system.host_batches[0]
data = tuple(mx.nd.array(a) for a in host)
xs, y = tr._place_batch(data, mx.nd.array(system._label))
lowered = tr._step_fn.trace(jax.random.PRNGKey(0), tr._values, tr._states,
                            tr._t + 1, tr._lr, *xs, y).lower(
    lowering_platforms=("tpu",))
text = lowered.as_text()
from jax._src.lib.mlir import ir
BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
bodies = [0]


def strip(m):
    raw = base64.b64decode(m.group(1))
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(raw).operation.get_asm(enable_debug_info=False)
    bodies[0] += 1
    return '\\22body\\22: \\22' + hashlib.sha256(asm.encode()).hexdigest() + '\\22'


def short(x):
    return hashlib.sha256(x.encode()).hexdigest()[:16]


text = BODY.sub(strip, text)
kernels = {}
for line in text.splitlines():
    name = re.search(r'kernel_name = "([^"]+)"', line)
    for body in BODY.findall(line):
        kernels.setdefault(name.group(1) if name else "?", set()).add(body)
# "hash": the whole text; "frame": the text with every kernel body left out;
# "kernels": each kernel name's distinct bodies, hashed together
print(json.dumps({"cell": cell_name, "over": over, "kernel_bodies": bodies[0],
                  "chars": len(text), "hash": short(text),
                  "frame": short(BODY.sub(lambda m: "", text)),
                  "kernels": {k: short(" ".join(sorted(v)))
                              for k, v in sorted(kernels.items())}}))
