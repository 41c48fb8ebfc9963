"""Meshes and per-chip peak rates.

Every mesh in the library, the tests and ``chip_smoke.py`` comes from
:func:`make_mesh`, which asks for ``AxisType.Auto`` axes: the pod
backend constrains shardings with ``with_sharding_constraint`` and
scatters into sharded client-state tables, both of which need Auto
axes (``jax.make_mesh`` defaults to Explicit ones).

Named mesh shapes:

  * :func:`make_host_mesh` — the degenerate 1×1 mesh on the first
    device (CPU tests, the library's default);
  * :func:`make_device_mesh` — every device JAX sees, as
    ``data × model`` (one chip: 1×1; a 2×2 v5e host: 2×2 with
    ``model=2``);
  * :func:`make_production_mesh` — the 16×16 single-pod and 2×16×16
    multi-pod specs the dry-run lowers against.

These are functions (not module constants) so that importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax call.

``CHIP_PEAKS`` holds the published peaks of each chip the roofline
reads, keyed by ``device_kind`` as JAX reports it; a device that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # B/s
    ici_bw: float               # B/s per link
    source: str


# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s inter-chip interconnect over
# four links (50 GB/s per link).
V5E = "TPU v5 lite"
CHIP_PEAKS = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9,
                   source="Google Cloud documentation, TPU v5e"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    name: str
    shape: tuple
    axes: tuple

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshSpec("pod", (16, 16), ("data", "model"))
MULTI_POD = MeshSpec("multipod", (2, 16, 16), ("pod", "data", "model"))


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    spec = MULTI_POD if multi_pod else SINGLE_POD
    return make_mesh(spec.shape, spec.axes)


def make_host_mesh():
    """Degenerate 1-device mesh (same axis names)."""
    return make_mesh((1, 1), ("data", "model"),
                     devices=jax.devices()[:1])


def make_device_mesh(model: int = 1, devices: Optional[Sequence] = None):
    """``data × model`` mesh over ``devices`` (default: all of
    ``jax.devices()``), ``model`` devices per tensor-parallel group."""
    devices = list(jax.devices() if devices is None else devices)
    if model < 1 or len(devices) % model:
        raise ValueError(f"model axis {model} does not divide "
                         f"{len(devices)} devices")
    return make_mesh((len(devices) // model, model), ("data", "model"),
                     devices=devices)
