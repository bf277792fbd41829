"""Production mesh construction (TPU v5e pods; host-device placeholders in CI).

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — used by repro.analysis.roofline
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 50e9                  # bytes/s per link


def make_mesh(shape: tuple, axes: tuple, devices=None):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings and
    ``with_sharding_constraint`` may name any axis (``jax.make_mesh`` makes
    ``Explicit`` axes by default, which such constraints refuse)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) > need:  # dry-run exposes 512 placeholders; single pod uses 256
        devs = devs[:need]
    return make_mesh(shape, axes, devices=devs)


def make_local_mesh(data: int = 1, model: int = 1, devices=None):
    """Small mesh over however many devices exist — tests/smoke runs."""
    return make_mesh((data, model), ("data", "model"), devices=devices)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
