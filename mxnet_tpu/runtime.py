"""Runtime feature introspection (reference ``python/mxnet/runtime.py`` over
`src/libinfo.cc` MXLibInfoFeatures — the compiled-feature-flag surface,
SURVEY §5.6 mech 3)."""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "[%s %s]" % ("✔" if self.enabled else "✖", self.name)


def _detect():
    import jax
    feats = {
        "TPU": any(d.platform != "cpu" for d in jax.devices()),
        "CPU": True,
        "XLA": True,
        "JIT": True,
        "AUTOGRAD": True,
        "BF16": True,
        "INT64_TENSOR_SIZE": True,
        "DIST_KVSTORE": True,       # XLA collectives (SURVEY §5.8)
        "RING_ATTENTION": True,
        "PALLAS": True,
        "CUDA": False, "CUDNN": False, "NCCL": False, "TENSORRT": False,
        "MKLDNN": False, "OPENCV": _has("PIL"),
        "OPENMP": True, "SSE": False, "F16C": False,
        "SIGNAL_HANDLER": True, "DEBUG": False,
    }
    return feats


def _has(mod):
    try:
        __import__(mod)
        return True
    except ImportError:
        return False


class Features(dict):
    """reference runtime.py Features — dict of Feature with is_enabled."""

    def __init__(self):
        super().__init__([(k, Feature(k, v)) for k, v in _detect().items()])

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("Feature '%s' is unknown" % feature_name)
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())
