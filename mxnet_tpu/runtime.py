"""Runtime feature detection. reference: python/mxnet/runtime.py
(`Features`, `feature_list`) over src/libinfo.cc (MXLibInfoFeatures) —
build-time flags surfaced at runtime. Here features are discovered live
from the JAX/PjRt environment.
"""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list", "is_enabled",
           "place_compile_cache"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "[%s: %s]" % ("✔" if self.enabled else "✖", self.name)


def _detect():
    import jax

    feats = {}
    platforms = set()
    try:
        for d in jax.devices():
            platforms.add(d.platform)
    except RuntimeError:
        pass
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["CUDA"] = "gpu" in platforms or "cuda" in platforms
    # the reference's vendor-kernel flags map to the XLA stack
    feats["CUDNN"] = False
    feats["MKLDNN"] = False
    feats["XLA"] = True
    try:
        from jax.experimental import pallas  # noqa: F401
        feats["PALLAS"] = True
    except ImportError:
        feats["PALLAS"] = False
    feats["BF16"] = True
    feats["INT64_TENSOR_SIZE"] = True
    feats["SIGNAL_HANDLER"] = False
    feats["PROFILER"] = True
    # multi-controller distributed (the dist_kvstore analog)
    feats["DIST_KVSTORE"] = True
    feats["OPENMP"] = False
    feats["SSE"] = False
    feats["F16C"] = False
    feats["JEMALLOC"] = False
    feats["OPENCV"] = False
    return feats


class Features(dict):
    """reference: runtime.py (Features) — dict of name → Feature."""

    instance = None

    def __init__(self):
        super().__init__([(k, Feature(k, v)) for k, v in _detect().items()])

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("Feature '%s' is unknown, known features are: "
                               "%s" % (feature_name, list(self.keys())))
        return self[feature_name].enabled


def feature_list():
    """reference: runtime.py (feature_list)."""
    if Features.instance is None:
        Features.instance = Features()
    return list(Features.instance.values())


def is_enabled(feature_name):
    if Features.instance is None:
        Features.instance = Features()
    return Features.instance.is_enabled(feature_name)


def place_compile_cache():
    """Give JAX's persistent compilation cache a directory that does not
    move between runs, and return it. The directory is part of the cache
    key, so one built from a pid, the time or `tempfile` never hits.

    Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing
    is set here; otherwise the cache goes to `.jax_cache/` at the root of
    the checkout. Entry points that compile whole models (chip_smoke.py,
    benchmark/run.py) call this before their first compile; `import
    mxnet_tpu` does not."""
    import os
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
