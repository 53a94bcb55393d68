"""Thread-local layout state + sharding hints for model code.

Model files call ``shard_hint(x, *axes)`` on intermediates with *logical*
axis tokens — ``"dp"`` (data-parallel), ``"model"`` (tensor/expert
parallel), or ``None`` — and this module resolves them against the active
layout to a ``PartitionSpec`` for ``jax.lax.with_sharding_constraint``.
When no mesh is active (1-device smoke tests, eager CPU runs) every hint
is an *exact identity*: the input object is returned unchanged.

Layouts name a token→mesh-axis mapping:

* ``"tp"`` (default) — ``dp`` → every mesh axis except ``model`` (so
  ``("data",)`` on a pod, ``("pod", "data")`` on multi-pod); ``model`` →
  the ``model`` axis (TP / expert parallel).
* ``"dp_only"`` — pure data parallel for small models on big meshes:
  ``dp`` → ``("data", "model")`` (the batch covers both axes, params stay
  replicated); ``model`` → the ``pod`` axis when present (context-DP: the
  sequence dim splits across pods) and nothing otherwise.

The active mesh comes from an explicit ``layout(mesh, ...)`` entry or,
failing that, from the ambient ``jax.set_mesh(mesh)`` context — so test
code that only does ``with jax.set_mesh(mesh): jax.jit(fn)(...)`` still
gets hints applied.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from .sharding import _axes_size as _mesh_axes_size

_DEFAULT_LAYOUT = "tp"

_state = threading.local()


@dataclasses.dataclass(frozen=True)
class _Layout:
    name: str
    mesh: Mesh | None


def _stack() -> list:
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def _ambient_mesh() -> AbstractMesh | None:
    """The mesh from an enclosing ``jax.set_mesh`` block, if any (its
    abstract form, which is also what a trace inside ``jax.jit`` sees)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _current_mesh() -> Mesh | AbstractMesh | None:
    for entry in reversed(_stack()):
        if entry.mesh is not None:
            return entry.mesh
    return _ambient_mesh()


def current_layout() -> str:
    st = _stack()
    return st[-1].name if st else _DEFAULT_LAYOUT


@contextlib.contextmanager
def layout(mesh_or_name: Mesh | str = _DEFAULT_LAYOUT,
           name: str | None = None):
    """Activate a layout: ``layout(mesh)``, ``layout("dp_only")``, or
    ``layout(mesh, "dp_only")``. Nestable; restores the previous layout
    (and mesh) on exit."""
    if isinstance(mesh_or_name, str):
        entry = _Layout(mesh_or_name, None)
    else:
        entry = _Layout(name or _DEFAULT_LAYOUT, mesh_or_name)
    st = _stack()
    st.append(entry)
    try:
        yield entry
    finally:
        st.pop()


@contextlib.contextmanager
def suspend_hints():
    """Make every ``shard_hint`` inside the block an identity (e.g. for
    code that runs under shard_map, where mesh axes are manual)."""
    _state.suspend = getattr(_state, "suspend", 0) + 1
    try:
        yield
    finally:
        _state.suspend -= 1


def _axis_map(mesh: Mesh, layout_name: str) -> dict:
    names = mesh.axis_names
    if layout_name == "dp_only":
        return {"dp": tuple(a for a in names if a in ("data", "model")),
                "model": "pod" if "pod" in names else None}
    return {"dp": tuple(a for a in names if a != "model"),
            "model": "model" if "model" in names else None}


def _axes_size(mesh: Mesh, axes) -> int:
    """sharding._axes_size, accepting None / a bare axis name too."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return _mesh_axes_size(mesh, axes)


def mesh_info() -> tuple[tuple[str, ...], int]:
    """(dp axis names, model-axis size) for the active layout.

    With no mesh active this is ``(("data",), 1)`` — callers use the size
    to pick single-device fallbacks, and never index the axis names into a
    mesh unless one exists.
    """
    mesh = _current_mesh()
    if mesh is None:
        return ("data",), 1
    amap = _axis_map(mesh, current_layout())
    return amap["dp"], _axes_size(mesh, amap["model"])


def shard_hint(x, *axes):
    """Constrain ``x`` (one token per dim: "dp" | "model" | mesh axis name
    | None) under the active layout; exact identity when no mesh is active,
    hints are suspended, or no token resolves to a >1-sized axis. Tokens
    that don't divide their dim are dropped per-dim rather than erroring —
    smoke shapes stay valid on any mesh."""
    if getattr(_state, "suspend", 0):
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    shape = getattr(x, "shape", None)
    if shape is None or len(shape) != len(axes):
        return x
    amap = _axis_map(mesh, current_layout())
    mesh_names = set(mesh.axis_names)
    used: set[str] = set()
    spec = []
    for dim, tok in zip(shape, axes):
        resolved = None
        if tok is not None:
            if tok in amap:
                resolved = amap[tok]
            elif tok in mesh_names:
                resolved = tok
        if resolved is not None:
            flat = (resolved,) if isinstance(resolved, str) else \
                tuple(resolved)
            size = _axes_size(mesh, flat)
            if (not flat or size <= 1 or dim % size
                    or used.intersection(flat)):
                resolved = None
            else:
                used.update(flat)
        spec.append(resolved)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
