"""Pallas TPU MoE dispatch (scatter) and combine (gather) kernels.

Dispatch scatters S tokens into the (n_slots, M) capacity buffer given
flat slot indices (expert * cap + slot, or n_slots for dropped tokens);
combine gathers them back weighted by the gate values.  Neither the
token matrix nor the capacity buffer fits VMEM at real widths, so both
kernels leave the side they address by index in HBM and move single
rows by DMA, with the indices scalar-prefetched into SMEM:

* dispatch tiles the *buffer*.  The (token, choice) pairs are sorted by
  slot once in jnp, so each buffer tile owns one contiguous run of
  pairs; the kernel pulls those token rows in chunks and adds each into
  its slot.  Adding (not overwriting) keeps the op a scatter-ADD,
  exact even on duplicate slots, which the gate never produces but the
  op contract allows.
* combine tiles the *tokens*.  Each token tile pulls its k buffer rows
  per token and mixes them with the gate weights (dropped choices carry
  weight zero, as in the oracle).

Row-addressed HBM arrays are viewed as (rows, 1, M) so that a one-row
DMA slices an untiled leading dim (any offset is legal there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(slot_ref, src_ref, bound_ref, x_hbm, o_ref, stage,
                     sems, *, block_n, chunk):
    t = pl.program_id(0)
    lo, hi = bound_ref[t], bound_ref[t + 1]
    o_ref[...] = jnp.zeros_like(o_ref)

    def run(c, carry):
        b = lo + c * chunk
        n = jnp.minimum(chunk, hi - b)

        def copy(i):
            return pltpu.make_async_copy(x_hbm.at[src_ref[b + i]],
                                         stage.at[i], sems.at[i])

        def start(i, _):
            copy(i).start()
            return _

        def wait(i, _):
            copy(i).wait()
            return _

        def add(i, _):
            r = slot_ref[b + i] - t * block_n
            o_ref[pl.ds(r, 1), :] += stage[i].astype(o_ref.dtype)
            return _

        lax.fori_loop(0, n, start, 0)
        lax.fori_loop(0, n, wait, 0)
        lax.fori_loop(0, n, add, 0)
        return carry

    lax.fori_loop(0, (hi - lo + chunk - 1) // chunk, run, 0)


def moe_dispatch(x, flat_idx, n_slots, *, block_s=256, interpret=None):
    """x: (S, M); flat_idx: (S, k) -> (n_slots, M) capacity buffer.

    ``block_s`` is the buffer tile (slots per grid step) and the row
    chunk pulled per DMA round."""
    S, M = x.shape
    k = flat_idx.shape[1]
    block_n = min(block_s, -(-n_slots // 8) * 8)
    n_tiles = -(-n_slots // block_n)
    chunk = min(block_s, S * k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    flat = flat_idx.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True)
    slot = flat[order]
    src = (order // k).astype(jnp.int32)
    # dropped pairs (slot == n_slots) sort past the last tile's bound
    bound = jnp.searchsorted(
        slot, jnp.arange(n_tiles + 1, dtype=jnp.int32) * block_n,
        side="left").astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, block_n=block_n, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_n, M), lambda t, *_: (t, 0)),
            scratch_shapes=[pltpu.VMEM((chunk, 1, M), x.dtype),
                            pltpu.SemaphoreType.DMA((chunk,))]),
        out_shape=jax.ShapeDtypeStruct((n_tiles * block_n, M), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="moe_dispatch",
        interpret=interpret,
    )(slot, src, bound, x.reshape(S, 1, M))
    return out[:n_slots] if n_tiles * block_n != n_slots else out


def _combine_kernel(idx_ref, buf_hbm, w_ref, o_ref, stage, sems, *,
                    block_s, k):
    base = pl.program_id(0) * block_s * k
    w = w_ref[...].astype(jnp.float32)                    # (bs, k)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(k):   # one round of row DMAs per routing choice
        def copy(i, j=j):
            return pltpu.make_async_copy(
                buf_hbm.at[idx_ref[base + i * k + j]], stage.at[i],
                sems.at[i])

        def start(i, _):
            copy(i).start()
            return _

        def wait(i, _):
            copy(i).wait()
            return _

        lax.fori_loop(0, block_s, start, 0)
        lax.fori_loop(0, block_s, wait, 0)
        acc = acc + w[:, j:j + 1] * stage[...].reshape(
            block_s, -1).astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def moe_combine(buf, flat_idx, weights, *, block_s=256, interpret=None):
    """buf: (n_slots, M); flat_idx/weights: (S, k) -> (S, M)."""
    n_slots, M = buf.shape
    S, k = flat_idx.shape
    block_s = min(block_s, -(-S // 8) * 8)
    s_pad = -(-S // block_s) * block_s
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # dropped choices read a real row with weight zero (the oracle's clamp)
    kept = flat_idx < n_slots
    idx = jnp.where(kept, flat_idx, n_slots - 1).astype(jnp.int32)
    w = jnp.where(kept, weights, 0.0).astype(buf.dtype)
    if s_pad != S:
        idx = jnp.pad(idx, ((0, s_pad - S), (0, 0)))
        w = jnp.pad(w, ((0, s_pad - S), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, block_s=block_s, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s_pad // block_s,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((block_s, k), lambda i, _: (i, 0))],
            out_specs=pl.BlockSpec((block_s, M), lambda i, _: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block_s, 1, M), buf.dtype),
                            pltpu.SemaphoreType.DMA((block_s,))]),
        out_shape=jax.ShapeDtypeStruct((s_pad, M), buf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="moe_combine",
        interpret=interpret,
    )(idx.reshape(-1), buf.reshape(n_slots, 1, M), w)
    return out[:S] if s_pad != S else out
