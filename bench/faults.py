"""Faults planted in the program's timed path, for the checks that
``correct`` catches each fault a training cell can have
(``tests/bench``) and for the fault readings that bound a cell's limits
(``bench/calibrate.py``).  The benchmark's own runs never plant one.

  unchanged     the step returns its state unchanged
  half_batch    the step sees the first half of the batch only, the loss
                its mean over those rows
  no_exchange   the exchange between chips left out: every all-to-all of
                the MoE layer keeps each chip's own rows
                (``repro.core.collectives``)
  no_mp_exchange  the MP all-reduce of the dense FFN left out: each MP
                rank keeps its partial sum of the row-parallel output
                (``repro.models.blocks.apply_ffn`` run per chip)

Each is planted while the program builds and traces its step (the
``Program`` is made inside :func:`planted`).
"""

from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "no_exchange", "no_mp_exchange")


def _unchanged(step):
    def unchanged(p, o, b):
        _, _, metrics = step(p, o, b)
        return p, o, metrics
    return unchanged


def _half_batch(step):
    def half(p, o, b):
        return step(p, o, {k: v[: v.shape[0] // 2] for k, v in b.items()})
    return half


class _LocalExchange:
    """``jax.lax`` with ``all_to_all`` kept on the chip: the chunks along
    ``split_axis`` are laid along ``concat_axis`` in order, each chip's
    own where the exchange would bring the others'."""

    def __init__(self, lax):
        self._lax = lax

    def __getattr__(self, name):
        return getattr(self._lax, name)

    def all_to_all(self, x, axis_name, split_axis, concat_axis, *,
                   tiled=False, **kw):
        import jax.numpy as jnp
        if not tiled:
            raise NotImplementedError("no_exchange plants tiled "
                                      "all-to-alls only")
        if split_axis == concat_axis:
            return x
        n = self._lax.axis_size(axis_name)
        return jnp.concatenate(jnp.split(x, n, axis=split_axis),
                               axis=concat_axis)


def _local_ffn(apply_ffn, local_mesh):
    """``apply_ffn`` under a ``shard_map`` over the mesh the program made
    last (by ``local_mesh``): weights split over MP as the program
    shards them, and the output taken as each chip's own partial sum,
    where GSPMD would all-reduce it over MP."""
    made = {}

    def recording_mesh(*a, **k):
        made["mesh"], made["dims"] = local_mesh(*a, **k)
        return made["mesh"], made["dims"]

    def ffn(p, x, act="silu"):
        import jax
        from jax.sharding import PartitionSpec as P
        mesh, dims = made["mesh"], made["dims"]
        mp, bx = tuple(dims.mp), tuple(dims.batch_axes) or None
        specs = {"w_in": P(None, mp), "w_gate": P(None, mp),
                 "w_out": P(mp, None), "b_in": P(mp), "b_out": P()}
        return jax.shard_map(
            lambda p, x: apply_ffn(p, x, act), mesh=mesh,
            in_specs=({k: specs[k] for k in p}, P(bx)), out_specs=P(bx),
            check_vma=False)(p, x)
    return recording_mesh, ffn


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` (one of FAULTS, or "" for none) in the program
    for the duration of the block."""
    if not fault:
        yield
        return
    if fault == "no_exchange":
        from repro.core import collectives as mod
        patches = [(mod, "lax", _LocalExchange(mod.lax))]
    elif fault == "no_mp_exchange":
        from repro.launch import mesh
        from repro.models import blocks
        made, ffn = _local_ffn(blocks.apply_ffn, mesh.local_mesh)
        patches = [(mesh, "local_mesh", made), (blocks, "apply_ffn", ffn)]
    elif fault in ("unchanged", "half_batch"):
        from repro.train import loop as mod
        real = mod.make_train_step
        wrap = _unchanged if fault == "unchanged" else _half_batch

        def broken(*a, **k):
            return wrap(real(*a, **k))
        patches = [(mod, "make_train_step", broken)]
    else:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    reals = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    for mod, name, broken in patches:
        setattr(mod, name, broken)
    try:
        yield
    finally:
        for mod, name, real in reals:
            setattr(mod, name, real)
