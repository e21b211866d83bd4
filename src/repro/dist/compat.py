"""Mesh construction, the ambient-mesh context and compiled cost analysis
on the installed jax.  Everything mesh-shaped in this repo goes through
these helpers instead of calling jax directly."""

from __future__ import annotations

from typing import Sequence, Tuple

__all__ = ["make_mesh", "mesh_context", "compiled_cost_analysis"]


def make_mesh(shape: Tuple[int, ...], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    import jax

    axes = tuple(axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def mesh_context(mesh):
    """Context manager making ``mesh`` ambient (``jax.set_mesh``)."""
    import jax

    return jax.set_mesh(mesh)


def compiled_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})
