"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* any jax
initialization.
"""

from __future__ import annotations

import jax

from repro.parallel.mesh import ParallelDims, make_mesh, production_dims


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Scaled-down mesh with the same axis structure (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def local_mesh(cfg, devices=None):
    """(mesh, dims) over the local devices (default: all of them),
    folded into (data, model): one device is (1, 1), n > 1 is
    (n // 2, 2).  MoE archs run EP over ``data`` and ESP == MP over
    ``model``; dense archs run DP over ``data`` and MP over ``model``."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    d = n // 2 if n > 1 else 1
    mesh = make_mesh((d, n // d), ("data", "model"), devices=devices)
    dims = (ParallelDims(ep=("data",), esp=("model",), mp=("model",))
            if cfg.moe is not None
            else ParallelDims(dp=("data",), mp=("model",)))
    return mesh, dims


def dims_for(cfg, multi_pod: bool = False) -> ParallelDims:
    """Logical parallel dims for an architecture on the production mesh."""
    return production_dims(multi_pod=multi_pod, moe=cfg.moe is not None)
