"""Production meshes.  A FUNCTION, not a module constant: importing this
module never touches jax device state (required by the dry-run contract)."""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, **kw):
    """``jax.make_mesh`` with every axis ``Auto``-typed: shardings are
    propagated by the compiler, as every sharded path here assumes."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_axes(mesh) -> dict:
    """Convenience: data-parallel axes tuple + model axis name."""
    names = tuple(mesh.axis_names)
    dp = tuple(a for a in names if a in ("pod", "data"))
    return {"dp": dp, "model": "model" if "model" in names else None,
            "all": names}


# Published peaks of ONE chip, the TPU v5e (Google Cloud documentation,
# "TPU v5e"), the target the LM/GNN dry-run roofline models from compiled
# cost analysis (benchmarks/roofline.py, perf_iter.py).  They hold for that
# chip only; no DBL path reads them.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (assignment-given constant)
CHIP_HBM_BYTES = 16 * 2**30   # v5e HBM capacity
