"""Production mesh construction.

A *function*, not a module constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).
"""

from __future__ import annotations

import jax


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]
          ) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ``pod`` (DP over DCN), ``data`` (FCP context parallel + FSDP),
    ``model`` (TP/EP).  ``jax.make_mesh`` assigns devices so that the
    trailing axes map to ICI-adjacent chips.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """Generic helper for tests/examples (e.g. (4, 2) x (data, model))."""
    return _mesh(shape, axes)
