"""Global device-mesh runtime — the substrate of all parallelism.

TPU-native replacement for the reference's process-group world
(paddle/fluid/distributed/collective/process_group.h:53 + NCCL comm caches,
process_group_nccl.cc:573): instead of N processes bootstrapping NCCL
communicators through a TCPStore, a single controller owns a
`jax.sharding.Mesh` whose named axes ARE the communicator groups. Every
"process group" of the reference maps to a mesh axis; every collective maps
to an XLA collective over that axis riding ICI (SURVEY §5.8 TPU-equivalent).

Axis-name conventions (mirrors fleet's 4D hybrid topology order,
fleet/base/topology.py:53, extended with sp/ep which the reference lacks):
  dp  — data parallel            (reference: dp degree)
  pp  — pipeline stages          (reference: pp degree)
  sdp — sharded data parallel    (reference: sharding degree, ZeRO)
  mp  — tensor/model parallel    (reference: mp degree)
  sp  — sequence/context parallel (exceeds reference; SURVEY §5.7)
  ep  — expert parallel          (reference: MoE global_scatter groups)
"""
from __future__ import annotations

import contextlib
import types
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# process-global (NOT thread-local: DataLoader worker threads and the main
# thread must see the same mesh; fleet.init happens once per process)
_state = types.SimpleNamespace()

HYBRID_AXES = ("dp", "pp", "sdp", "mp")  # reference 4D order (topology.py:53)


def _get(name, default=None):
    return getattr(_state, name, default)


def build_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from {axis_name: degree}; degrees must multiply to ndev.

    Axis order in `axes` is the physical layout order: the LAST axis varies
    fastest over adjacent devices, so put the heaviest-communication axis
    (mp/sp) last to keep its collectives on nearest-neighbour ICI — same
    logic as the reference giving mp the fastest-varying ranks
    (fleet/base/topology.py hybrid order).
    """
    if devices is None:
        devices = jax.devices()
    shape = tuple(axes.values())
    n = int(np.prod(shape)) if shape else 1
    if n != len(devices):
        raise ValueError(
            f"mesh axes {axes} require {n} devices, have {len(devices)}")
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, tuple(axes.keys()))


def set_mesh(mesh: Optional[Mesh]):
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    """The process-global mesh (None until init_parallel_env/fleet.init)."""
    return _get("mesh")


def mesh_axis_size(axis: str) -> int:
    m = get_mesh()
    if m is None or axis not in m.axis_names:
        return 1
    return m.shape[axis]


def filter_spec(*entries):
    """PartitionSpec with axis names not present in the active mesh replaced
    by None — lets model code write its full sharding intent (dp/mp/sp/...)
    once and degrade gracefully on smaller meshes."""
    m = get_mesh()
    names = set(m.axis_names) if m is not None else set()

    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(x for x in e if x in names)
            return kept if kept else None
        return e if e in names else None

    return P(*[keep(e) for e in entries])


@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def named_sharding(*spec) -> Optional[NamedSharding]:
    m = get_mesh()
    if m is None:
        return None
    return NamedSharding(m, P(*spec))


def shard_constraint(arr, *spec):
    """with_sharding_constraint if a mesh is active and we are inside a
    trace; no-op otherwise. Used by parallel layers to pin activation
    layouts (the declarative analog of the reference's explicit
    _c_identity/_mp_allreduce calls in mpu/mp_ops.py:27-219)."""
    m = get_mesh()
    if m is None:
        return arr
    try:
        return jax.lax.with_sharding_constraint(arr, NamedSharding(m, filter_spec(*spec)))
    except (ValueError, TypeError):
        return arr


def _auto_axes(m):
    """Axes of mesh `m` that no enclosing shard_map has made manual."""
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return {a for a in m.axis_names if a not in manual}


def kernel_axes(args, in_specs):
    """The mesh axes `shard_kernel` will really split `args` over: those
    `in_specs` name that the active mesh has with degree > 1, that are
    not already manual, and whose degree divides every dim they name."""
    m = get_mesh()
    if m is None:
        return frozenset()
    axes = {a for a in _auto_axes(m) if m.shape[a] > 1}
    for arg, spec in zip(args, in_specs):
        for dim, name in zip(arg.shape, spec):
            if name in axes and dim % m.shape[name]:
                axes.discard(name)
    return frozenset(n for spec in in_specs for n in spec if n in axes)


def shard_kernel(fn, args, in_specs, out_spec):
    """Call a Pallas kernel on arrays that live on the active mesh.

    A Mosaic kernel is opaque to the SPMD partitioner ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), so under a mesh `fn(*args)` runs per shard inside a
    shard_map over every mesh axis that is not manual already. Each spec
    is a tuple of axis names (or None) per dim and names only the axes
    the kernel is independent over — batch over dp, heads over mp; a name
    `kernel_axes` drops leaves that dim whole on every shard, which is
    redundant work, never a wrong answer. fn returns one array, laid out
    as `out_spec` says. Off-mesh this is fn(*args)."""
    m = get_mesh()
    auto = _auto_axes(m) if m is not None else None
    if not auto:
        return fn(*args)
    split = kernel_axes(args, in_specs)

    def to_p(spec):
        return P(*[n if n in split else None for n in spec])

    return jax.shard_map(fn, mesh=m, axis_names=auto,
                         in_specs=tuple(map(to_p, in_specs)),
                         out_specs=to_p(out_spec), check_vma=False)(*args)
