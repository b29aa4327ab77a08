"""Mesh construction for single-pod and multi-pod deployments.

Production target: TPU v5e-256 pods (16 x 16 chips); multi-pod couples 2 pods
over DCN.  Axes:

  pod    - data parallelism across pods (gradient all-reduce over DCN;
           optionally int8-compressed, see repro.optim.compression)
  data   - data parallelism within a pod (batch sharding, ZeRO-1)
  model  - tensor/expert parallelism (heads, d_ff, vocab, experts, and
           sequence-sharded KV caches for decode)

These are FUNCTIONS (not module constants) so importing never touches jax
device state - jax locks the device count on first backend initialization.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The dry-run / deployment mesh: (16, 16) or (2, 16, 16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A mesh whose axes are all ``Auto``: shardings propagate through the
    program as GSPMD places them (``jax.make_mesh`` defaults to ``Explicit``
    axes, under which the embedding gather needs an ``out_sharding``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: Optional[int] = None):
    """Best-effort mesh over whatever devices exist (CPU smoke tests, elastic
    restarts after losing hosts): (data, model) with model_axis dividing the
    device count.

    An explicit ``model_axis`` is CLAMPED to the largest divisor of the
    device count that does not exceed it (asking for model=8 on a 1-device
    host yields the trivial (1, 1) mesh, not the degenerate (0, 8) shape the
    unclamped division used to produce)."""
    n = len(jax.devices())
    if model_axis is None:
        model_axis = 1
        for cand in (16, 8, 4, 2):
            if n % cand == 0 and n >= cand:
                model_axis = cand
                break
    else:
        if model_axis < 1:
            raise ValueError(f"model_axis must be >= 1, got {model_axis}")
        model_axis = min(model_axis, n)
        while n % model_axis != 0:
            model_axis -= 1
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def parse_mesh_shape(spec: str) -> Tuple[int, int]:
    """Parse an ``RxC`` mesh flag ("1x8" -> (1, 8)): (data, model) axes."""
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"mesh spec must be RxC (e.g. 1x8), got {spec!r}")
    try:
        data, model = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"mesh spec must be RxC with integer axes, got {spec!r}") from None
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return data, model


def make_serve_mesh(data: int, model: int):
    """A (data, model) mesh over the FIRST data*model devices.

    Unlike :func:`make_host_mesh` this takes the requested shape literally
    (the serve engine's sharded jit closures are traced against it), but it
    tolerates the process holding MORE devices than the mesh needs - e.g. a
    (1, 4) serve mesh inside an 8-host-device test process."""
    need = data * model
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(
            f"mesh {data}x{model} needs {need} devices; only "
            f"{len(devs)} available")
    from jax.sharding import Mesh

    return Mesh(np.array(devs[:need]).reshape(data, model),
                ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh (('pod','data') when multi-pod)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def dp_size(mesh) -> int:
    return int(np.prod([axis_size(mesh, a) for a in dp_axes(mesh)]))
