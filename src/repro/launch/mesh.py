"""Production mesh definitions.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax init).

  single-pod: (16, 16)      axes ("data", "model")   = 256 chips (one v5e pod)
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Hardware constants (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI,
16 GiB HBM) are OWNED by ``repro.roofline.analysis`` — the launch layer
re-exports them for compatibility so the dry-run report and the roofline
table can never disagree on what a chip is.
"""
from __future__ import annotations

import jax

from repro.roofline.analysis import (  # noqa: F401  (compat re-exports)
    CHIP_HBM_BYTES,
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    num_chips,
)


def make_mesh_compat(shape, axes, **kwargs):
    """``jax.make_mesh`` with Auto axes (the sharding rules annotate with
    ``with_sharding_constraint``, which needs Auto, and ``jax.make_mesh``
    does not default to it). All mesh construction in this repo funnels
    through here."""
    kwargs.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axes))
    return jax.make_mesh(shape, axes, **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh():
    """1-device mesh with the production axis names — smoke tests and
    benches run the same model code without 512 fake devices."""
    return make_mesh_compat((1, 1), ("data", "model"))


def make_data_mesh(shards: int | None = None):
    """1-axis ``("data",)`` mesh over ``shards`` devices (default: all) —
    the serving mesh of the row-sharded searcher family
    (``repro.search`` ``*_sharded`` backends): the corpus partitions over
    "data" and each device scans only its local CSR shard."""
    n = jax.device_count() if shards is None else shards
    return make_mesh_compat((n,), ("data",))
