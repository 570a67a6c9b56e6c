"""The one ``shard_map`` entry point of the tree.

Every call site goes through :func:`shard_map` here so the manual-axis
set is always named explicitly (``axis_names=`` — never leaning on
empty-set-means-all) and ``check_vma`` is always an explicit literal the
``unsafe-shard-map`` lint rule can see.  Partial-manual regions (some
axes left AUTO, e.g. the pp x sp composition in ``parallel/pipeline.py``)
derive their manual set from the plan-built mesh via
:func:`manual_axes_except`.

The literal ``check_vma=False`` pins at the four pallas call sites stay
visible to the lint rule (and keep their ``# lint: jax-version-pinned``
escapes live) because the call sites are still named ``shard_map``.
"""

from typing import Optional

import jax


def manual_axes_except(mesh, *auto_axes: str) -> frozenset:
    """The manual-axis set for a partial-manual region: every mesh axis
    except ``auto_axes``.  One helper so call sites derive the set from
    the mesh the plan built (parallel/plan.py) instead of hand-listing
    axis names — a plan that grows an axis (the 'pod' DCN tier did
    exactly this) then flows through automatically."""
    return frozenset(mesh.shape) - frozenset(auto_axes)


def inside_manual_region(axes) -> bool:
    """True while tracing inside a ``shard_map`` that already holds any of
    ``axes`` manually (e.g. ``parallel/hierarchy.py``'s full-manual region
    over the dp tier).  JAX refuses a second ``shard_map`` over an axis
    that is already Manual, so a router that would open one asks first."""
    held = jax.sharding.get_abstract_mesh().manual_axes
    return bool(frozenset(axes) & frozenset(held))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma,
              manual_axes: Optional[frozenset] = None):
    """``jax.shard_map`` with the manual axes always spelled out.

    ``manual_axes=None`` means full-manual over every mesh axis; a set
    leaves the remaining axes AUTO (partial-manual).  ``check_vma`` is
    REQUIRED: defaulting it off would let a future call site disable
    checking silently, where the ``unsafe-shard-map`` lint can only see
    (and demand a pin justification for) an explicit literal ``False``.
    """
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=(
            frozenset(mesh.shape)
            if manual_axes is None
            else frozenset(manual_axes)
        ),
        check_vma=check_vma,
    )
