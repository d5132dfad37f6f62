"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any
device query; see launch/dryrun.py).

Single pod: 256 chips as (data=16, model=16) -- TP stays inside the pod's
ICI. Multi-pod: (pod=2, data=16, model=16); the ``pod`` axis is the
DCN-connected dimension and only ever carries data-parallel gradient
reductions (optionally int8-compressed, optim/grad_compress.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "make_frames_mesh",
           "data_axes", "DATA_AXES", "MODEL_AXIS", "FRAMES_AXIS"]

MODEL_AXIS = "model"
FRAMES_AXIS = "frames"


def make_frames_mesh(num_devices: int | None = None, *,
                     axis_name: str = FRAMES_AXIS):
    """1-D serving mesh for sharded frame rendering.

    The frame axis of the batched ASK scan pipeline
    (``core.ask.run_ask_scan_sharded`` / ``mandelbrot.solve_batch(...,
    mesh=...)``) shards over this mesh's single axis. Defaults to every
    visible device; pass ``num_devices`` to carve out a prefix (the
    render-service benchmarks pit a 1-device mesh against the full host
    complement).
    """
    n = len(jax.devices()) if num_devices is None else int(num_devices)
    return _auto_mesh((n,), (axis_name,))


def make_production_mesh(*, multi_pod: bool = False,
                         model_split: int | None = None):
    """Default: (data, model) = (16, 16) per pod. ``model_split=s`` factors
    the model axis into (model_a=s, model_b=16//s) -- 2-D tensor
    parallelism for archs whose head count doesn't divide 16 (whisper: 20
    heads shard 4-way on model_a while FFN/vocab use the full 16;
    EXPERIMENTS.md Sec. Perf extras)."""
    if model_split:
        ms = (model_split, 16 // model_split)
        shape = (2, 16, *ms) if multi_pod else (16, *ms)
        axes = (("pod", "data", "model_a", "model_b") if multi_pod
                else ("data", "model_a", "model_b"))
        return _auto_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use tiny ones, elastic restarts reshape)."""
    return _auto_mesh(tuple(shape), tuple(axes))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding code here
    relies on GSPMD propagation and ``with_sharding_constraint``, which
    the ``Explicit`` default of JAX 0.9 rejects."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a mesh: every non-model axis."""
    return tuple(a for a in mesh.axis_names if not a.startswith("model"))


def model_axes(mesh) -> tuple:
    """The tensor-parallel axes: ('model',) or ('model_a', 'model_b')."""
    return tuple(a for a in mesh.axis_names if a.startswith("model"))


DATA_AXES = ("pod", "data")  # superset; data_axes(mesh) filters per mesh
