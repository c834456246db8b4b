"""Device-mesh construction and scoping.

Replaces the reference's device-list plumbing (`Module(context=[gpu(0),...])`,
`kvstore 'device'` comm topology in src/kvstore/comm.h) with a named
`jax.sharding.Mesh`. A mesh axis name is the unit of parallelism: 'data' for
DP, 'model' for TP, 'seq' for sequence/context parallelism, 'expert' for MoE.
"""
from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field

import numpy as _np

import jax
from jax.sharding import Mesh, PartitionSpec

__all__ = ["MeshConfig", "create_mesh", "local_mesh", "auto_mesh",
           "current_mesh", "mesh_scope"]

_STATE = threading.local()


@dataclass
class MeshConfig:
    """Declarative mesh shape. Axes with size 1 are kept (harmless) so
    PartitionSpecs can always name them.

    data:  data-parallel (batch) axis — gradients psum over this.
    fsdp:  parameter-sharding axis (ZeRO-3 / FSDP); params all-gathered
           per-layer on use. Merged with `data` for plain DP when 1.
    model: tensor-parallel axis (Megatron column/row splits).
    seq:   sequence/context-parallel axis (ring attention).
    expert: expert-parallel axis. Declared, and still unused: the one
           expert layer (`ops/moe.py`, `models/qwen3_next.py`) is told
           which experts its chip holds and runs on one chip without the
           exchange; no `all_to_all` over this axis and no rule in
           `parallel/sharding.py` places experts on it yet.
    """
    data: int = 1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    expert: int = 1
    axis_order: tuple = ("data", "fsdp", "seq", "model", "expert")

    def sizes(self):
        return tuple(getattr(self, a) for a in self.axis_order)

    @property
    def n_devices(self):
        n = 1
        for s in self.sizes():
            n *= s
        return n


def _default_devices(n_needed):
    """Default device list for a mesh that needs `n_needed` devices.

    When MXNET_MESH_HOST_FALLBACK=1 (set by the on-chip test harness,
    tests/conftest.py) and the default backend has fewer devices than the
    mesh needs — e.g. a single real chip vs an 8-way mesh test — fall
    back to the virtual host-CPU devices so multi-device code paths still
    execute. Production code never sets the gate: too few devices stays
    a hard error."""
    devices = jax.devices()
    if (len(devices) < n_needed
            and os.environ.get("MXNET_MESH_HOST_FALLBACK", "0") == "1"):
        try:
            host = jax.devices("cpu")
        except RuntimeError:
            return devices
        if len(host) >= n_needed:
            return host
    return devices


def create_mesh(config=None, devices=None, **axes):
    """Build a Mesh from a MeshConfig or axis kwargs.

    ``create_mesh(data=4, model=2)`` → 8-device mesh with axes
    ('data','fsdp','seq','model','expert') sized (4,1,1,2,1). ICI-friendly:
    axis order puts 'model' innermost-but-one so TP collectives ride
    nearest-neighbor links.
    """
    if config is None:
        config = MeshConfig(**axes)
    n = config.n_devices
    if devices is None:
        devices = _default_devices(n)
    if n > len(devices):
        raise ValueError(
            "mesh needs %d devices but only %d available" % (n, len(devices)))
    dev_array = _np.asarray(devices[:n]).reshape(config.sizes())
    return Mesh(dev_array, config.axis_order)


def local_mesh(n_devices=None, axis="data"):
    """1-D mesh over (the first n) local devices — the analog of the
    reference's single-process multi-GPU `kvstore='device'` setup."""
    devices = _default_devices(n_devices or 1)
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(_np.asarray(devices), (axis,))


def auto_mesh(model_parallel=1, seq_parallel=1, fsdp=False):
    """Pick a sensible mesh for all visible devices: fills the remaining
    factor with data (or fsdp) parallelism."""
    devices = _default_devices(model_parallel * seq_parallel)
    n = len(devices)
    rest = n // (model_parallel * seq_parallel)
    if rest * model_parallel * seq_parallel != n:
        raise ValueError(
            "%d devices not divisible by model=%d x seq=%d"
            % (n, model_parallel, seq_parallel))
    cfg = MeshConfig(
        data=1 if fsdp else rest, fsdp=rest if fsdp else 1,
        model=model_parallel, seq=seq_parallel)
    return create_mesh(cfg, devices=devices)


def current_mesh():
    """The innermost active mesh (mesh_scope), or None."""
    stack = getattr(_STATE, "stack", None)
    if stack:
        return stack[-1]
    return None


@contextlib.contextmanager
def mesh_scope(mesh):
    """`with mesh_scope(mesh):` — sets both our thread-local current mesh and
    jax's global mesh context (so bare PartitionSpecs in shard_map resolve)."""
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        stack.pop()
