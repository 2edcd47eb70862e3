"""Device and mesh abstraction.

Replaces the reference's Place/DeviceContext machinery
(``paddle/phi/common/place.h``, ``paddle/phi/backends/gpu/gpu_context.h:84``)
and the fleet 5-axis topology (``fleet/base/topology.py:66`` axes
[data, pipe, sharding, sep, model]) with jax devices + ``jax.sharding.Mesh``.

XLA owns streams/allocators on TPU; what remains framework-level is (a) device
listing/selection, (b) a process-global current mesh with the canonical hybrid
axes, and (c) per-axis group info (rank/size) mirroring HybridCommunicateGroup.
"""

from __future__ import annotations

import contextlib
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "get_device", "set_device", "device_count", "is_compiled_with_tpu",
    "HYBRID_AXES", "make_mesh", "current_mesh", "use_mesh", "axis_size",
    "HybridTopology", "trimmed_spec",
]

P = PartitionSpec

# Canonical hybrid-parallel axes, matching the reference's 5-D topology
# (fleet/base/topology.py:66-69): data, pipe, sharding(fsdp), sep(sequence), model(tp).
HYBRID_AXES = ("dp", "pp", "fsdp", "sep", "mp")

_current_mesh: list[Mesh | None] = [None]
_current_device: list[jax.Device | None] = [None]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def get_device() -> jax.Device:
    return _current_device[0] or jax.devices()[0]


def set_device(device: str | jax.Device) -> jax.Device:
    """Accepts 'tpu:0' / 'cpu:1' style strings (parity: paddle.set_device)."""
    if isinstance(device, str):
        if ":" in device:
            platform, idx = device.split(":")
            device = jax.devices(platform)[int(idx)]
        else:
            device = jax.devices(device)[0]
    _current_device[0] = device
    return device


def trimmed_spec(*entries) -> PartitionSpec:
    """PartitionSpec with trailing Nones dropped — the way jax spells the
    shardings a jitted program returns. jit's cache key compares specs
    structurally: an input placed with ``P('mp', None)`` and the step
    output carrying ``P('mp')`` are one layout but two keys, so a step fed
    its own outputs would compile a second time. Place with this spelling
    and the program count stays pinned (serving step programs, the meshed
    train step)."""
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def make_mesh(
    axis_sizes: Sequence[int] | dict[str, int],
    axis_names: Sequence[str] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh. ``make_mesh({'dp':2,'mp':4})`` or ``make_mesh((2,4), ('dp','mp'))``.

    Axis order follows the convention: outermost axes map across hosts/DCN,
    innermost across ICI — put 'mp'/'sep' innermost for bandwidth-hungry
    collectives (the declarative analogue of the reference's ordered
    CommunicateTopology axes).
    """
    if isinstance(axis_sizes, dict):
        axis_names = tuple(axis_sizes.keys())
        sizes = tuple(axis_sizes.values())
    else:
        sizes = tuple(axis_sizes)
        if axis_names is None:
            axis_names = HYBRID_AXES[: len(sizes)]
    devs = list(devices) if devices is not None else jax.devices()
    n = int(np.prod(sizes))
    if n > len(devs):
        raise ValueError(f"mesh needs {n} devices, have {len(devs)}")
    arr = np.array(devs[:n]).reshape(sizes)
    return Mesh(arr, tuple(axis_names))


def current_mesh() -> "Mesh | jax.sharding.AbstractMesh | None":
    """Active mesh: this library's use_mesh stack, else the ambient jax
    mesh. While tracing under an ambient ``set_mesh`` scope the return is
    an AbstractMesh (no concrete mesh exists on the trace context) —
    callers may rely on ``.shape``/``.axis_names`` and shard_map, not on
    ``.devices`` or ``with mesh:``."""
    if _current_mesh[0] is not None:
        return _current_mesh[0]
    # fall back to the ambient jax mesh so callers that gate on an active
    # mesh (e.g. MoE sorted-dispatch fallback) see meshes activated without
    # this library's use_mesh wrapper: the jax.sharding.set_mesh context
    # first, then the legacy `with mesh:` thread resources (private
    # import — the public pxla alias is deprecated; guarded so removal just
    # disables the legacy bridge, never the set_mesh path).
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        # while tracing under jit there is no concrete mesh on the trace
        # context (jax.sharding.get_mesh raises there); callers only
        # inspect .shape/.axis_names or feed shard_map, all of which
        # accept the abstract mesh
        try:
            cm = jax.sharding.get_mesh()
        except ValueError:
            return am
        return cm if isinstance(cm, Mesh) and not cm.empty else am
    try:
        from jax._src.mesh import thread_resources
        pm = thread_resources.env.physical_mesh
        if not pm.empty:
            return pm
    except Exception:
        pass
    return None


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = _current_mesh[0]
    _current_mesh[0] = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _current_mesh[0] = prev


def axis_size(name: str, mesh: Mesh | None = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


class HybridTopology:
    """Per-axis rank/size bookkeeping over a Mesh.

    Parity: ``HybridCommunicateGroup`` (fleet/base/topology.py:178) — but
    declarative: groups are mesh axes, collectives are compiled by XLA, so no
    communicator objects are created here.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def get_parallel_degree(self, axis: str) -> int:
        return axis_size(axis, self.mesh)

    @property
    def dp_degree(self):
        return self.get_parallel_degree("dp")

    @property
    def mp_degree(self):
        return self.get_parallel_degree("mp")

    @property
    def pp_degree(self):
        return self.get_parallel_degree("pp")

    @property
    def sharding_degree(self):
        return self.get_parallel_degree("fsdp")

    @property
    def sep_degree(self):
        return self.get_parallel_degree("sep")

    def named_sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))
