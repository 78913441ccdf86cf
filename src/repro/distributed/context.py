"""Process-global mesh / sharding-rule context.

Model code annotates activations with *logical* axis names; the launcher
installs a mesh + rule table mapping logical names to mesh axes.  Outside a
mesh context every annotation is a no-op, so the same model code runs on a
laptop CPU and on a 512-chip multi-pod mesh unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

_STATE = threading.local()


def _get():
    if not hasattr(_STATE, "mesh"):
        _STATE.mesh, _STATE.rules = None, None
    return _STATE


def set_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None) -> None:
    s = _get()
    s.mesh, s.rules = mesh, rules


def get_mesh() -> Optional[Mesh]:
    return _get().mesh


def get_rules() -> Optional[dict]:
    return _get().rules


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis of type Auto.

    Model code places activations with ``with_sharding_constraint`` and
    leaves the rest to sharding propagation; on ``jax.make_mesh``'s default
    Explicit axes those constraints become type assertions and gathers of
    sharded tables need spelled-out output shardings."""
    if mesh.are_all_axes_auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: dict):
    prev = (_get().mesh, _get().rules)
    mesh = auto_axes(mesh)
    set_mesh(mesh, rules)
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        set_mesh(*prev)


def resolve_axis(logical: Optional[str], size: int) -> Optional[object]:
    """Pick the first candidate mesh-axis (or axis tuple) that divides size.

    rules[logical] is a preference list like [('model',), ('data', 'model'),
    ()]; an empty tuple means replicate.  Returns a PartitionSpec entry.
    """
    s = _get()
    if logical is None or s.rules is None or s.mesh is None:
        return None
    sizes = dict(zip(s.mesh.axis_names, s.mesh.devices.shape))
    for cand in s.rules.get(logical, [()]):
        if not cand:
            return None
        if any(ax not in sizes for ax in cand):
            continue  # rule references an axis this mesh doesn't have
        prod = 1
        for ax in cand:
            prod *= sizes[ax]
        if size % prod == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


def _resolve_consuming(logical: Optional[str], size: int, used: set):
    """First-fit resolution that skips candidates whose mesh axes are taken.

    A PartitionSpec may name each mesh axis at most once; tensors whose
    logical axes *both* prefer the same mesh axis (e.g. kv_heads and head_dim
    -> 'model') get the first dim that fits, and the later dim falls through
    to its next candidate (often replication).  This is the divisibility /
    conflict fallback rule table mechanism of DESIGN.md §5.
    """
    s = _get()
    if logical is None or s.rules is None or s.mesh is None:
        return None
    sizes = dict(zip(s.mesh.axis_names, s.mesh.devices.shape))
    for cand in s.rules.get(logical, [()]):
        if not cand:
            return None
        if any(ax in used or ax not in sizes for ax in cand):
            continue
        prod = 1
        for ax in cand:
            prod *= sizes[ax]
        if size % prod == 0:
            used.update(cand)
            return cand if len(cand) > 1 else cand[0]
    return None


def pspec_for(shape: Sequence[int], logical_axes: Sequence[Optional[str]]
              ) -> PartitionSpec:
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    used: set = set()
    return PartitionSpec(*[_resolve_consuming(a, d, used)
                           for d, a in zip(shape, logical_axes)])


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axes (no-op without a mesh)."""
    s = _get()
    if s.mesh is None or s.rules is None:
        return x
    spec = pspec_for(x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(s.mesh, spec))


def named_sharding(shape, logical_axes) -> Optional[NamedSharding]:
    s = _get()
    if s.mesh is None:
        return None
    return NamedSharding(s.mesh, pspec_for(shape, logical_axes))
