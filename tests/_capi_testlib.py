"""Shared helpers for the C-ABI test files: repo/lib paths and the
build-or-skip gate (one `make -C src` site instead of one per file)."""
import os
import pathlib
import subprocess

REPO = pathlib.Path(__file__).resolve().parent.parent
LIB = REPO / "lib" / "libmxtpu_c.so"


def built():
    if LIB.exists():
        return True
    r = subprocess.run(["make", "-C", str(REPO / "src")],
                       capture_output=True, text=True)
    return r.returncode == 0 and LIB.exists()


def host_env():
    """Environment for spawned C hosts: CPU platform, single device."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env
