"""Pallas TPU dropless grouped expert FFN (ragged + fully-fused forms).

Two kernels implement the Parm grouped-GEMM megakernel seam:

``expert_ffn_ragged``
    The pool-path form: the (E, G, c, M) receive buffer from the
    dispatch AlltoAll plus per-(expert, group) routed-row counts.  The
    counts are scalar-prefetched into SMEM, so every token tile whose
    rows are entirely beyond the routed count is *predicated off* with
    ``pl.when`` — the MXU never sees it, so compute scales with routed
    tokens, not capacity ("dropless" in FLOPs) — and the index maps pin
    such tiles onto blocks already resident, so they cost no DMA
    either.  Partially-valid tiles mask their tail rows to exact zero,
    matching the oracle bit-for-bit.  Compute runs in f32 (the decode
    half of the fused wire codec when the A2A payload arrives raw bf16)
    and the output is cast back to the input dtype (the encode half for
    the combine A2A).

``expert_ffn_grouped``
    The single-device megakernel: dispatch gather fused into the
    prologue, the two expert GEMMs and activation in the body, and the
    combine scatter + gate-weight dot fused into the epilogue — one
    kernel launch, no (n_slots, M) intermediates in HBM.  The token
    matrix and the (S, M) f32 result stay in HBM: slot -> token row ids
    are scalar-prefetched into SMEM, each active capacity tile DMAs its
    routed rows in, and the epilogue read-modify-writes the output rows
    it owns (a token appears at most once per expert, so the rows of
    one tile are distinct; the grid runs sequentially, so tiles never
    race).  Empty slots point at a spare row past the end of the
    output, which is sliced off.  ``wire`` in {"f32", "bf16"} applies
    the wire-codec round-trip at the two pool boundaries so the fused
    op is numerically identical to dispatch -> encode/decode -> FFN ->
    encode/decode -> combine.

Rows move between HBM and VMEM one at a time, so the row-addressed
arrays are viewed as (rows, 1, M): a DMA of one row is then a slice of
an untiled leading dim, which the TPU's DMA engine takes at any offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def _ffn_tile(x, w1_ref, w3_ref, w2_ref, act):
    """One (bt, M) f32 token tile through one hidden slice of the FFN."""
    h = lax.dot_general(x, w1_ref[0].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if w3_ref is not None:
        h = ACT[act](h) * lax.dot_general(
            x, w3_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        h = ACT[act](h)
    return lax.dot_general(h, w2_ref[0].astype(jnp.float32),
                           (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tiles(cap, F, block_t, block_f):
    block_t = min(block_t, -(-cap // 8) * 8)
    block_f = min(block_f, F)
    while F % block_f:
        block_f //= 2
    c_pad = -(-cap // block_t) * block_t
    return block_t, block_f, c_pad


def _ragged_kernel(cnt_ref, x_ref, w1_ref, *refs, act, glu, block_t, G):
    if glu:
        w3_ref, w2_ref, o_ref = refs
    else:
        w3_ref = None
        w2_ref, o_ref = refs
    e, g = pl.program_id(0), pl.program_id(1)
    it, jf = pl.program_id(2), pl.program_id(3)

    @pl.when(jf == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    cnt = cnt_ref[e * G + g]

    @pl.when(it * block_t < cnt)          # ragged: skip empty tiles
    def _compute():
        x = x_ref[0, 0].astype(jnp.float32)               # (bt, M)
        out = _ffn_tile(x, w1_ref, w3_ref, w2_ref, act)
        rows = it * block_t + lax.broadcasted_iota(
            jnp.int32, (block_t, 1), 0)
        out = jnp.where(rows < cnt, out, 0.0)  # mask tail of partial tile
        o_ref[...] += out.astype(o_ref.dtype)[None, None]


def expert_ffn_ragged(xb, counts, w1, w3, w2, *, act="silu", block_t=128,
                      block_f=256, interpret=None):
    """xb: (E, G, c, M) pool; counts: (E, G) int32 routed rows per group;
    w1/w3: (E, M, F); w2: (E, F, M) -> (E, G, c, M) in xb.dtype."""
    E, G, c, M = xb.shape
    F = w1.shape[-1]
    glu = w3 is not None
    block_t, block_f, c_pad = _tiles(c, F, block_t, block_f)
    if c_pad != c:
        xb = jnp.pad(xb, ((0, 0), (0, 0), (0, c_pad - c), (0, 0)))
    n_t, n_f = c_pad // block_t, F // block_f
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def live(e, g, it, jf, cnt):
        # an empty tile re-points at the last block the previous live
        # tile used, so the pipeline issues no DMA for it
        n_live = (cnt[e * G + g] + block_t - 1) // block_t
        ok = it < n_live
        return (jnp.where(ok, it, jnp.maximum(n_live - 1, 0)),
                jnp.where(ok, jf, n_f - 1))

    def x_map(e, g, it, jf, cnt):
        return (e, g, live(e, g, it, jf, cnt)[0], 0)

    def w_in_map(e, g, it, jf, cnt):
        return (e, 0, live(e, g, it, jf, cnt)[1])

    def w_out_map(e, g, it, jf, cnt):
        return (e, live(e, g, it, jf, cnt)[1], 0)

    w_in_spec = pl.BlockSpec((1, M, block_f), w_in_map)
    in_specs = [
        pl.BlockSpec((1, 1, block_t, M), x_map),
        w_in_spec,
        *([w_in_spec] if glu else []),
        pl.BlockSpec((1, block_f, M), w_out_map),
    ]
    operands = (xb, w1, w3, w2) if glu else (xb, w1, w2)

    out = pl.pallas_call(
        functools.partial(_ragged_kernel, act=act, glu=glu,
                          block_t=block_t, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, G, n_t, n_f),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_t, M),
                                   lambda e, g, it, jf, cnt: (e, g, it, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, G, c_pad, M), xb.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="expert_ffn_ragged",
        interpret=interpret,
    )(counts.reshape(-1).astype(jnp.int32), *operands)
    return out[:, :, :c] if c_pad != c else out


def slot_metadata(flat_idx, weights, n_tokens, n_experts, cap):
    """Invert the gate's (token -> slot) map into the kernel's
    (slot -> token) form: per-slot source row ids (sentinel =
    ``n_tokens`` for empty slots), per-slot gate weights, and per-expert
    routed-row counts.  Slots are contiguous per expert (GShard slot
    priority), so counts are exactly the ragged group sizes."""
    S, k = flat_idx.shape
    flat = flat_idx.reshape(-1)
    src = (jnp.arange(S * k, dtype=jnp.int32) // k)
    rid = jnp.full((n_experts * cap,), n_tokens, jnp.int32)
    rid = rid.at[flat].set(src, mode="drop")
    ws = jnp.zeros((n_experts * cap,), jnp.float32)
    ws = ws.at[flat].set(weights.reshape(-1).astype(jnp.float32),
                         mode="drop")
    counts = jnp.sum((rid < n_tokens).reshape(n_experts, cap), axis=1,
                     dtype=jnp.int32)
    return (rid.reshape(n_experts, cap), ws.reshape(n_experts, cap),
            counts)


def _fused_kernel(rid_ref, cnt_ref, x_hbm, ws_ref, w1_ref, *refs,
                  act, glu, block_t, c_pad, n_f, S, wire):
    if glu:
        w3_ref, w2_ref, y_in, y_hbm, xg, acc, yb, sems = refs
    else:
        w3_ref = None
        w2_ref, y_in, y_hbm, xg, acc, yb, sems = refs
    del y_in                                     # aliased to y_hbm
    e, it, jf = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base = e * c_pad + it * block_t
    active = it * block_t < cnt_ref[e]

    def rt(v):        # fused wire round-trip at a pool boundary
        return v.astype(jnp.bfloat16).astype(v.dtype) if wire == "bf16" \
            else v

    def rows(copy):   # one DMA per tile row, each on its own semaphore
        def start(i, c):
            copy(i).start()
            return c

        def wait(i, c):
            copy(i).wait()
            return c
        lax.fori_loop(0, block_t, start, 0)
        lax.fori_loop(0, block_t, wait, 0)

    @pl.when(active & (jf == 0))
    def _gather():     # dispatch prologue: pull routed rows into the tile
        rows(lambda i: pltpu.make_async_copy(
            x_hbm.at[jnp.minimum(rid_ref[base + i], S - 1)], xg.at[i],
            sems.at[i]))
        acc[...] = jnp.zeros_like(acc)

    @pl.when(active)
    def _compute():
        x = rt(xg[...].reshape(block_t, -1).astype(jnp.float32))
        acc[...] += _ffn_tile(x, w1_ref, w3_ref, w2_ref, act)

    @pl.when(active & (jf == n_f - 1))
    def _scatter():    # combine epilogue: weight-dot + scatter-add
        # empty slots hold the sentinel S: the spare row y[S]
        rows(lambda i: pltpu.make_async_copy(
            y_hbm.at[rid_ref[base + i]], yb.at[i], sems.at[i]))
        out = rt(acc[...]) * ws_ref[0]                    # (bt, M)
        yb[...] += out.reshape(yb.shape)
        rows(lambda i: pltpu.make_async_copy(
            yb.at[i], y_hbm.at[rid_ref[base + i]], sems.at[i]))


def expert_ffn_grouped(x, flat_idx, weights, w1, w3, w2, *, cap,
                       act="silu", wire="f32", block_t=128, block_f=256,
                       interpret=None):
    """Fused dispatch -> ragged FFN -> combine. x: (S, M);
    flat_idx/weights: (S, k); returns (S, M) in x.dtype."""
    S, M = x.shape
    E, _, F = w1.shape
    glu = w3 is not None
    block_t, block_f, c_pad = _tiles(cap, F, block_t, block_f)
    n_t, n_f = c_pad // block_t, F // block_f
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    rid, ws, counts = slot_metadata(flat_idx, weights, S, E, cap)
    if c_pad != cap:
        pad = ((0, 0), (0, c_pad - cap))
        rid = jnp.pad(rid, pad, constant_values=S)
        ws = jnp.pad(ws, pad)

    def w_map(e, it, jf, rid, cnt):
        # empty tiles keep the previous tile's hidden slice: no DMA
        return jnp.where(it * block_t < cnt[e], jf, n_f - 1)

    w_in_spec = pl.BlockSpec((1, M, block_f),
                             lambda e, it, jf, rid, cnt:
                             (e, 0, w_map(e, it, jf, rid, cnt)))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        hbm,
        pl.BlockSpec((1, block_t, 1), lambda e, it, jf, rid, cnt:
                     (e, it, 0)),
        w_in_spec,
        *([w_in_spec] if glu else []),
        pl.BlockSpec((1, block_f, M), lambda e, it, jf, rid, cnt:
                     (e, w_map(e, it, jf, rid, cnt), 0)),
        hbm,
    ]
    operands = (x.reshape(S, 1, M), ws[..., None], w1,
                *((w3,) if glu else ()), w2,
                jnp.zeros((S + 1, 1, M), jnp.float32))

    y = pl.pallas_call(
        functools.partial(_fused_kernel, act=act, glu=glu,
                          block_t=block_t, c_pad=c_pad, n_f=n_f, S=S,
                          wire=wire),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, n_t, n_f),
            in_specs=in_specs,
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((block_t, 1, M), x.dtype),      # gathered rows
                pltpu.VMEM((block_t, M), jnp.float32),     # FFN acc
                pltpu.VMEM((block_t, 1, M), jnp.float32),  # output rows
                pltpu.SemaphoreType.DMA((block_t,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((S + 1, 1, M), jnp.float32),
        # the zero-filled output enters as the last operand (after the
        # two scalar-prefetch arrays) and is updated in place
        input_output_aliases={len(operands) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="expert_ffn_grouped",
        interpret=interpret,
    )(rid.reshape(-1), counts, *operands)
    return y[:S, 0].astype(x.dtype)
