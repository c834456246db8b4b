"""Persistent AOT executable cache.

The fleet cold-start problem (ROADMAP item #2): a thousand serving replicas
— or one elastic worker resuming after preemption — each recompile every
program from scratch at startup, and `telemetry.note_compile` measures the
storm. This module serializes *compiled* executables to disk
(`jax.experimental.serialize_executable`, the PjRt executable-serialization
API underneath jax's own compilation cache) keyed by a tracelint-style
signature (graph/program hash + shapes/dtypes + mesh + jax/library
versions), so the second process skips XLA entirely and loads the binary.

Operational contract, in order of importance:

* **Never errs.** A corrupted, truncated, or version-skewed entry is a
  counted miss (`compiler.cache.corrupt`) followed by a normal recompile —
  a bad cache can cost time, never correctness or a crash.
* **Atomic writes.** Entries land via write-to-temp + `os.replace`, so
  concurrent writers (a fleet warming the same shared directory) are
  last-write-wins and readers never observe a half-written file.
* **Version-keyed.** `key_for` folds jax/jaxlib/library versions, backend
  platform, and device count into every key, so an upgraded worker misses
  instead of loading an executable compiled for a different runtime.
* **Bounded.** keep=N eviction (`MXNET_TPU_AOT_CACHE_KEEP`, oldest-mtime
  first) after every store.

Enabled by pointing `MXNET_TPU_AOT_CACHE` at a directory; off by default
(the cache is a deployment optimization, not a semantic change).

**Trust model.** Entries are pickles (that is what the PjRt
serialization API hands back), and loading one executes it. The sha256
framing detects *corruption* — a torn write, a truncated copy — not
*tampering*: anyone who can write the cache directory can make every
reader run arbitrary code. Point `MXNET_TPU_AOT_CACHE` only at
directories writable solely by principals you already trust to run code
on these machines (the same trust you place in the model checkpoint and
the package itself); never at a world-writable or untrusted-shared path.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import time

from .. import telemetry as _telem

__all__ = ["AOTCache", "aot_cache", "cache_key", "hlo_hash",
           "load_or_compile"]

# entry layout: MAGIC + sha256(payload) + payload; the digest makes
# truncation/corruption detection exact rather than "pickle happened to
# throw"
_MAGIC = b"MXAOT1\n"
_SUFFIX = ".aotx"
_DEFAULT_KEEP = 32


def _versions():
    """The runtime identity every key embeds: an executable is only
    portable between processes running the same compiler stack on the
    same topology."""
    import jax
    import jaxlib
    from ..base import __version__ as _mx_version
    try:
        n_dev = jax.device_count()
        platform = jax.devices()[0].platform
    except Exception:  # backend not initialized / unreachable
        n_dev, platform = 0, "unknown"
    return {
        "jax": getattr(jax, "__version__", "?"),
        "jaxlib": getattr(jaxlib, "__version__", "?"),
        "mxnet_tpu": _mx_version,
        "platform": platform,
        "device_count": n_dev,
    }


def _canon(obj):
    """Canonicalize key parts into something json can serialize stably."""
    if isinstance(obj, dict):
        return {str(k): _canon(obj[k]) for k in sorted(obj, key=str)}
    if isinstance(obj, (list, tuple)):
        return [_canon(x) for x in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if isinstance(obj, bytes):
        return hashlib.sha256(obj).hexdigest()
    return repr(obj)


def cache_key(**parts):
    """Hex digest over canonical-json key parts + the runtime versions."""
    parts["__runtime__"] = _versions()
    blob = json.dumps(_canon(parts), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def avals_sig(tree):
    """Shapes/dtypes of a pytree of arrays/ShapeDtypeStructs, as a
    key-part (paths included so two trees with equal leaves but different
    structure key differently)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return {
        "tree": str(treedef),
        "leaves": [[list(getattr(x, "shape", ())),
                    str(getattr(x, "dtype", type(x).__name__))]
                   for x in leaves],
    }


class AOTCache:
    """One cache directory of serialized executables."""

    def __init__(self, path=None, keep=None):
        from ..base import get_env
        if path is None:
            path = get_env("MXNET_TPU_AOT_CACHE", "") or None
        self.path = path
        if keep is None:
            keep = int(get_env("MXNET_TPU_AOT_CACHE_KEEP", _DEFAULT_KEEP))
        self.keep = keep

    @property
    def enabled(self):
        return bool(self.path)

    # ------------------------------------------------------------- load
    def load(self, key, label="program"):
        """The executable stored under `key`, deserialized and loaded onto
        the current backend — or None (counted miss). Corruption of any
        kind (bad magic, digest mismatch, unpicklable, executable rejected
        by the runtime) is a counted `compiler.cache.corrupt` + miss,
        never an exception."""
        if not self.enabled:
            return None
        fname = os.path.join(self.path, key + _SUFFIX)
        t0 = time.perf_counter()
        try:
            with open(fname, "rb") as f:
                blob = f.read()
        except OSError:
            _telem.inc("compiler.cache.misses")
            return None
        try:
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            digest = blob[len(_MAGIC):len(_MAGIC) + 64]
            payload = blob[len(_MAGIC) + 64:]
            if hashlib.sha256(payload).hexdigest().encode() != digest:
                raise ValueError("checksum mismatch")
            meta, serialized, in_tree_b, out_tree_b = pickle.loads(payload)
            # the executable goes back onto the devices it was compiled
            # for, in their order (a mesh's order is part of the program):
            # left to itself the runtime loads it over every device of the
            # process and the first call wants a shard for each
            import jax
            by_id = {d.id: d for d in jax.devices()}
            try:
                devices = [by_id[i] for i in meta["device_ids"]]
            except KeyError:
                # written before ids were recorded, or for a device this
                # process lacks: not this process's entry
                _telem.inc("compiler.cache.misses")
                return None
            from jax.experimental import serialize_executable as _se
            loaded = _se.deserialize_and_load(
                serialized, pickle.loads(in_tree_b), pickle.loads(out_tree_b),
                execution_devices=devices)
        except Exception:
            # a bad entry must cost a recompile, not a crash — count it
            # and treat as a miss (the next store overwrites it)
            _telem.inc("compiler.cache.corrupt")
            _telem.inc("compiler.cache.misses")
            return None
        if isinstance(meta, dict) and meta.get("memory_analysis"):
            # replay the static footprint recorded at compile time: a warm
            # restore reports memory_analysis WITHOUT recompiling (the
            # ledger's fleet cold-start evidence)
            from ..telemetry import ledger as _ledger
            _ledger.note_program(label, meta["memory_analysis"],
                                 cached=True)
        _telem.inc("compiler.cache.hits")
        _telem.observe("compiler.cache.load_ms",
                       (time.perf_counter() - t0) * 1e3)
        _telem.note_compile("%s[cached]" % label)
        return loaded

    # ------------------------------------------------------------ store
    def store(self, key, compiled, label="program", meta=None):
        """Serialize `compiled` (a jax.stages.Compiled) under `key`.
        Atomic (temp + rename): concurrent writers are last-write-wins and
        a reader can never see a partial entry. Returns True on success;
        serialization failures are counted, never raised."""
        if not self.enabled:
            return False
        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as _se
            serialized, in_tree, out_tree = _se.serialize(compiled)
            # the same object `serialize` pickles: its device list is the
            # program's own assignment, whatever else the process holds
            device_ids = [d.id for d in compiled._executable
                          ._unloaded_executable.device_list]
            payload = pickle.dumps(
                (dict(meta or {}, label=label, versions=_versions(),
                      device_ids=device_ids),
                 serialized, pickle.dumps(in_tree), pickle.dumps(out_tree)))
        except Exception:
            _telem.inc("compiler.cache.serialize_error")
            return False
        blob = _MAGIC + hashlib.sha256(payload).hexdigest().encode() + payload
        fname = os.path.join(self.path, key + _SUFFIX)
        try:
            os.makedirs(self.path, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.path,
                                       suffix=_SUFFIX + ".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, fname)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception:
            _telem.inc("compiler.cache.write_error")
            return False
        _telem.inc("compiler.cache.writes")
        _telem.observe("compiler.cache.store_ms",
                       (time.perf_counter() - t0) * 1e3)
        self._evict()
        return True

    def _evict(self):
        """keep=N retention, oldest mtime first. Unlink races with other
        evicting processes are benign (someone removed it for us)."""
        if self.keep <= 0:
            return
        try:
            entries = []
            for name in os.listdir(self.path):
                if not name.endswith(_SUFFIX):
                    continue
                full = os.path.join(self.path, name)
                try:
                    entries.append((os.path.getmtime(full), full))
                except OSError:
                    continue
            entries.sort()
            for _, full in entries[:-self.keep] if len(entries) > self.keep \
                    else []:
                try:
                    os.unlink(full)
                    _telem.inc("compiler.cache.evictions")
                except OSError:
                    pass
        except OSError:
            pass


def hlo_hash(lowered):
    """sha256 of a lowered program's HLO text — the program half of the
    key for sites (train steps) that key on the exact traced
    computation rather than a graph/geometry signature."""
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def load_or_compile(key, lower_fn, label, meta=None):
    """The compile-or-restore step every AOT rider shares (whole-graph
    executor, serve warmup, train steps): a warm hit returns
    (restored executable, True) without calling `lower_fn`; a miss
    calls it, compiles, stores, and returns (executable, False).
    Site-specific telemetry (`serve.compile`, `*.aot_restored`, ...)
    stays with the callers — they count different things.

    The miss branch harvests `compiled.memory_analysis()` into the HBM
    ledger AND into the cache entry's meta, so the hit branch (another
    process, a warm restart) replays the same footprint without a
    recompile — see telemetry/ledger.py."""
    from ..telemetry import ledger as _ledger
    cache = aot_cache()
    ex = cache.load(key, label)
    if ex is not None:
        return ex, True
    compiled = lower_fn().compile()
    footprint = _ledger.harvest(compiled)
    _ledger.note_program(label, footprint)
    meta = dict(meta or {})
    if footprint:
        meta["memory_analysis"] = footprint
    cache.store(key, compiled, label, meta=meta)
    return compiled, False


# process-level accessor: one AOTCache per MXNET_TPU_AOT_CACHE value, so
# tests (and long-lived processes) that flip the env var get a fresh view
_GLOBAL = {"path": None, "cache": None}


def aot_cache():
    """The process AOT cache (rebuilt if MXNET_TPU_AOT_CACHE changed)."""
    from ..base import get_env
    path = get_env("MXNET_TPU_AOT_CACHE", "") or None
    if _GLOBAL["cache"] is None or _GLOBAL["path"] != path:
        _GLOBAL["path"] = path
        _GLOBAL["cache"] = AOTCache(path)
    return _GLOBAL["cache"]
