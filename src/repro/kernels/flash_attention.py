"""Pallas TPU flash attention (GQA-aware, causal + sliding window).

The kernel runs head-major: q is laid out (B, H, L, hd) and k/v
(B, K, L, hd), so every block is ``(block, hd)`` in its last two dims —
the TPU's (8, 128) tiling rule holds for any head dim, because a block
dim equal to the array dim is always legal.  The public entry keeps the
model's (B, L, H, hd) layout and transposes at the boundary.

Grid (B, H, nq, nk) with the KV-block index innermost; online-softmax
running stats (m, l) and the output accumulator live in VMEM scratch and
carry across the nk iterations.  The index map folds the query-head ->
kv-head mapping (h // rep), so no head replication ever hits HBM.  KV
blocks lying wholly outside the causal/window band are skipped, and
their index map is clamped into the band so they are not fetched
either.

Block shapes default to (128, 128): MXU-aligned on the (q, k) tile and
sized so q/k/v tiles + accumulator fit comfortably in VMEM for head dims
up to 256.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kv_band(iq, *, causal, window, block_q, block_k, n_k):
    """First and last KV block index any query of block ``iq`` attends."""
    lo, hi = 0, n_k - 1
    if causal:
        hi = jnp.minimum(hi, (iq * block_q + block_q - 1) // block_k)
    if window is not None:
        lo = jnp.maximum(lo, (iq * block_q - window + 1) // block_k)
    return lo, hi


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, causal, window, block_q, block_k, n_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    lo, hi = _kv_band(iq, causal=causal, window=window, block_q=block_q,
                      block_k=block_k, n_k=n_k)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((ik >= lo) & (ik <= hi))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        q_pos = iq * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ik * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = jnp.ones(s.shape, dtype=jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=128, block_k=128, interpret=None):
    """q: (B, Lq, H, hd); k, v: (B, Lk, K, hd) with H % K == 0."""
    B, Lq, H, hd = q.shape
    _, Lk, K, _ = k.shape
    assert H % K == 0, (H, K)
    rep = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    while Lq % block_q:
        block_q //= 2
    while Lk % block_k:
        block_k //= 2
    n_q, n_k = Lq // block_q, Lk // block_k
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    band = functools.partial(_kv_band, causal=causal, window=window,
                             block_q=block_q, block_k=block_k, n_k=n_k)

    def kv_index(b, h, iq, ik):
        lo, hi = band(iq)
        return (b, h // rep, jnp.clip(ik, lo, hi), 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), kv_index)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
