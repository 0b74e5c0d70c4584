"""Pallas TPU grouped expert FFN (the MoE compute hot-spot).

One kernel fuses both expert matmuls and the activation:
    out[e] = (act(x[e] @ w1[e]) [* (x[e] @ w3[e])]) @ w2[e]

Grid (E, nT, nF): expert-major, token tile (block_t) second, hidden tile
(block_f) innermost; the (block_t, M) output accumulator is revisited
across the nF iterations (constant index map on the F axis), so the
second matmul accumulates in VMEM and each w1/w3/w2 hidden slice is read
from HBM exactly once.  Tiles are MXU-aligned (128) on every contraction
dim; M stays unblocked (fits VMEM for M <= ~8k at block_f = 128-512).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def _ffn_kernel(x_ref, w1_ref, *refs, act, glu):
    # the w3 operand only exists in the GLU variant (no dead operand is
    # staged into VMEM for the 2-layer FFN)
    if glu:
        w3_ref, w2_ref, o_ref = refs
    else:
        w2_ref, o_ref = refs
    jf = pl.program_id(2)

    @pl.when(jf == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0].astype(jnp.float32)                      # (bt, M)
    w1 = w1_ref[0].astype(jnp.float32)                    # (M, bf)
    h = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())))
    if glu:
        w3 = w3_ref[0].astype(jnp.float32)
        h = ACT[act](h) * jax.lax.dot_general(
            x, w3, (((1,), (0,)), ((), ())))
    else:
        h = ACT[act](h)
    w2 = w2_ref[0].astype(jnp.float32)                    # (bf, M)
    o_ref[...] += jax.lax.dot_general(
        h, w2, (((1,), (0,)), ((), ()))).astype(o_ref.dtype)[None]


def expert_ffn(x, w1, w3, w2, *, act="silu", block_t=128, block_f=256,
               interpret=None):
    """x: (E, T, M); w1/w3: (E, M, F); w2: (E, F, M) -> (E, T, M)."""
    E, T, M = x.shape
    F = w1.shape[-1]
    glu = w3 is not None
    block_t = min(block_t, T)
    block_f = min(block_f, F)
    # Token dim: pad up to the MXU-aligned tile instead of shrinking the
    # tile to a divisor (non-power-of-two T used to degrade block_t all
    # the way to 1 — scalar-width MXU issue).  The pad rows compute
    # garbage that is sliced off below; they never alias real tokens.
    t_pad = -(-T // block_t) * block_t
    if t_pad != T:
        x = jnp.pad(x, ((0, 0), (0, t_pad - T), (0, 0)))
    while F % block_f:
        block_f //= 2
    n_t, n_f = t_pad // block_t, F // block_f
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(_ffn_kernel, act=act, glu=glu)
    w_in_spec = pl.BlockSpec((1, M, block_f), lambda e, it, jf: (e, 0, jf))
    in_specs = [
        pl.BlockSpec((1, block_t, M), lambda e, it, jf: (e, it, 0)),
        w_in_spec,
        *([w_in_spec] if glu else []),
        pl.BlockSpec((1, block_f, M), lambda e, it, jf: (e, jf, 0)),
    ]
    operands = (x, w1, w3, w2) if glu else (x, w1, w2)

    out = pl.pallas_call(
        kernel,
        grid=(E, n_t, n_f),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_t, M), lambda e, it, jf: (e, it, 0)),
        out_shape=jax.ShapeDtypeStruct((E, t_pad, M), x.dtype),
        name="expert_ffn",
        interpret=interpret,
    )(*operands)
    return out[:, :T] if t_pad != T else out
