"""Mesh construction and logical parallel-dimension bookkeeping.

Parm's schedules are expressed over four *logical* parallel dimensions —
DP (pure data parallel), EP (expert parallel), ESP (expert-sharding
parallel) and MP (tensor/model parallel) — each mapped onto one or more
physical mesh axes.  The production mesh maps EP onto ``data`` and both
MP and ESP onto ``model`` (the DeepSpeed-TED setting, N_MP == N_ESP);
unit tests build dedicated ``(dp, ep, esp, mp)`` meshes to exercise
N_MP != N_ESP, which the paper's Table III explores.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, names, devices=None) -> Mesh:
    """``jax.make_mesh`` pinned to Auto axis types (GSPMD + shard_map mix),
    over ``devices`` (default: all of them)."""
    names = tuple(names)
    return jax.make_mesh(tuple(int(s) for s in shape), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def axis_size(mesh: Mesh, axes) -> int:
    """Product of sizes of ``axes`` (a name or tuple of names) in ``mesh``."""
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


@dataclass(frozen=True)
class ParallelDims:
    """Mapping of logical parallel dims to physical mesh axis names.

    ``esp == mp`` (and non-empty) is the *merged* mode used on the
    production mesh: the ESP group coincides with the MP group, so the
    baseline schedule's ESP-AllGather materializes N_MP identical copies
    of the dispatch buffer — exactly the redundancy Parm eliminates.
    """

    dp: tuple = ()   # pure data-parallel axes (gradient all-reduce)
    ep: tuple = ()   # expert-parallel axes (AlltoAll dispatch/combine)
    esp: tuple = ()  # expert-sharding axes (expert FFN hidden dim)
    mp: tuple = ()   # tensor/model-parallel axes (dense Megatron sharding)

    def __post_init__(self):
        for f in ("dp", "ep", "esp", "mp"):
            v = getattr(self, f)
            if isinstance(v, str):
                object.__setattr__(self, f, (v,))
            else:
                object.__setattr__(self, f, tuple(v))

    @property
    def merged(self) -> bool:
        """True when the ESP group is the MP group (DeepSpeed-TED setting)."""
        return len(self.mp) > 0 and self.esp == self.mp

    @property
    def batch_axes(self) -> tuple:
        """Axes over which tokens are distinct at the MoE-layer boundary.

        In merged mode MP(==ESP) ranks hold replicated activations; in the
        distinct-axes mode, ESP ranks double as extra data parallelism
        (they hold different tokens), which is what gives the baseline's
        ESP-AllGather its B*L*M*N_ESP cost in the paper's Eq. (1).
        """
        if self.merged:
            return self.dp + self.ep
        return self.dp + self.ep + self.esp

    def sizes(self, mesh: Mesh) -> dict:
        return {
            "dp": axis_size(mesh, self.dp),
            "ep": axis_size(mesh, self.ep),
            "esp": axis_size(mesh, self.esp),
            "mp": axis_size(mesh, self.mp),
        }

    def validate(self, mesh: Mesh, n_experts: int) -> None:
        for a in self.dp + self.ep + self.esp + self.mp:
            if a not in mesh.shape:
                raise ValueError(f"axis {a!r} not in mesh {mesh.shape}")
        n_ep = axis_size(mesh, self.ep)
        if n_experts % max(n_ep, 1) != 0:
            raise ValueError(
                f"E={n_experts} must be divisible by EP degree {n_ep}")


# Canonical logical->physical mappings ---------------------------------------

def production_dims(multi_pod: bool = False, moe: bool = True) -> ParallelDims:
    """Logical dims for the (16,16) / (2,16,16) production meshes.

    MoE archs: EP over ``data`` (DeepSpeed-MoE style "EP inside DP"),
    ESP == MP over ``model``; the ``pod`` axis is pure DP.
    Dense archs: MP over ``model``, everything else DP.
    """
    dp = ("pod",) if multi_pod else ()
    if moe:
        return ParallelDims(dp=dp, ep=("data",), esp=("model",), mp=("model",))
    return ParallelDims(dp=dp + ("data",), ep=(), esp=(), mp=("model",))
