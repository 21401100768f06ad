"""Every mesh the repo builds comes from :func:`make_mesh`.  Functions (never
module-level constants) so importing this module does not touch jax device
state.

``jax.make_mesh`` makes ``Explicit`` axes by default since jax 0.7; the
engines place state with ``NamedSharding`` and jit under the ``Auto``
sharding model, so the helper always asks for ``Auto`` axes."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh with ``Auto`` axes of ``shape`` named ``axes``; over
    ``devices`` in the given order when passed, else over the default
    devices in the order ``jax.make_mesh`` picks."""
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    return Mesh(np.asarray(list(devices)).reshape(shape), axes,
                axis_types=auto)


def has_explicit_axes(mesh: Mesh) -> bool:
    return any(t == AxisType.Explicit for t in mesh.axis_types)

