"""Pallas TPU flash attention (GQA-aware, causal + sliding window).

The kernel runs head-major: q is laid out (B, H, L, hd) and k/v
(B, K, L, hd), so every block is ``(heads, block, hd)`` in its last
three dims — the TPU's (8, 128) tiling rule holds for any head dim,
because a block dim equal to the array dim is always legal.  The public
entry keeps the model's (B, L, H, hd) layout and transposes at the
boundary.

Grid (B, H // hb, nq, nk) with the KV-block index innermost: each step
does the work of ``hb`` query heads, one (block_q, block_k) tile each,
as dots batched over the head axis.  A grid step has a fixed cost (DMA
issue and wait, bookkeeping) of a fraction of a microsecond, about as
much as one head's 128 x 128 tile of work, so one head a step left the
kernel stepping, not computing.  (On a v5e the batched dots ran 12%
faster than a static loop over the heads at gpt2-moe's shape.)  The
q/out block is ``(1, hb, block_q, hd)`` and the k/v block ``(1, hb //
rep, block_k, hd)``: the KV heads those query heads read (query head j
of the block reads KV head j // rep, repeated in VMEM), so no head
replication ever hits HBM.  Online-softmax running stats (m, l),
``(hb, block_q, 1)``, and the output accumulator, ``(hb, block_q,
hd)``, live in VMEM scratch and carry across the nk iterations.  KV
blocks lying wholly outside the causal/window band are skipped, and
their index map is clamped into the band so they are not fetched
either.

``hb`` follows the call's shape (:func:`heads_per_step`): the largest
divisor of H that is a multiple of ``rep = H // K`` and whose
double-buffered q/k/v/out blocks, scratch, ``(hb, block_q, block_k)``
score tile and (for GQA) k/v repeated to ``hb`` heads fit
``VMEM_BUDGET``, counting lanes as padded to 128.  At gpt2-moe's (H=12,
hd=64) that is all twelve heads, 9.0 MiB; a four-chip shard of it (H=6
or 3) takes all of its heads too.  The kernel asks Mosaic for
``VMEM_LIMIT`` of scoped VMEM, above the default, so the budget leaves
room for the compiler's own temporaries.

Block shapes default to (128, 128): MXU-aligned on the (q, k) tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

NEG_INF = -1e30
LANES = 128                  # a VMEM tile's minor dim pads to this
VMEM_BUDGET = 24 * 2 ** 20   # what heads_per_step lets one step hold
VMEM_LIMIT = 48 * 2 ** 20    # scoped VMEM asked of Mosaic


def _lanes(n):
    return -(-n // LANES) * LANES


def step_vmem_bytes(hb, rep, hd, block_q, block_k, itemsize):
    """VMEM one grid step of ``hb`` query heads holds: q/out and k/v
    blocks, each double-buffered, the f32 scratch (m, l, acc), one f32
    score tile per head and, where ``rep`` > 1, f32 k/v repeated to
    ``hb`` heads; the minor dim padded to 128 lanes."""
    lane_hd = _lanes(hd)
    blocks = 2 * itemsize * lane_hd * (2 * hb * block_q
                                       + 2 * (hb // rep) * block_k)
    scratch = 4 * hb * block_q * (2 * LANES + lane_hd)
    scores = 4 * hb * block_q * _lanes(block_k)
    repeated = 2 * 4 * hb * block_k * lane_hd if rep > 1 else 0
    return blocks + scratch + scores + repeated


def heads_per_step(H, K, hd, block_q, block_k, itemsize=4):
    """Query heads one grid step takes: the largest divisor of H that is
    a multiple of ``rep = H // K`` and whose step fits ``VMEM_BUDGET``
    (``rep`` itself when none does)."""
    rep = H // K
    fits = [hb for hb in range(rep, H + 1, rep)
            if H % hb == 0 and step_vmem_bytes(
                hb, rep, hd, block_q, block_k, itemsize) <= VMEM_BUDGET]
    return max(fits, default=rep)


def _kv_band(iq, *, causal, window, block_q, block_k, n_k):
    """First and last KV block index any query of block ``iq`` attends."""
    lo, hi = 0, n_k - 1
    if causal:
        hi = jnp.minimum(hi, (iq * block_q + block_q - 1) // block_k)
    if window is not None:
        lo = jnp.maximum(lo, (iq * block_q - window + 1) // block_k)
    return lo, hi


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, causal, window, block_q, block_k, n_k, rep):
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
        q = q_ref[0].astype(jnp.float32) * scale          # (hb, bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (hb/rep, bk, hd)
        v = v_ref[0].astype(jnp.float32)
        if rep > 1:   # KV head j serves query heads j*rep .. j*rep+rep-1
            k = jnp.repeat(k, rep, axis=0)
            v = jnp.repeat(v, rep, axis=0)
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
        shape = (block_q, block_k)
        q_pos = iq * block_q + lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = ik * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
        ok = jnp.ones(shape, dtype=jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


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
    hb = heads_per_step(H, K, hd, block_q, block_k, q.dtype.itemsize)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    band = functools.partial(_kv_band, causal=causal, window=window,
                             block_q=block_q, block_k=block_k, n_k=n_k)
    with jax.ensure_compile_time_eval():
        lo, hi = band(jnp.arange(n_q))
        in_band = int(jnp.sum(jnp.broadcast_to(hi - lo + 1, (n_q,))))
    obs.emit("kernel.grid", kernel="flash_attention", heads_per_step=hb,
             grid_steps=B * (H // hb) * n_q * n_k,
             in_band_steps=B * (H // hb) * in_band)

    def kv_index(b, g, iq, ik):
        lo, hi = band(iq)
        return (b, g, jnp.clip(ik, lo, hi), 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, rep=rep)
    q_spec = pl.BlockSpec((1, hb, block_q, hd),
                          lambda b, g, iq, ik: (b, g, iq, 0))
    kv_spec = pl.BlockSpec((1, hb // rep, block_k, hd), kv_index)

    out = pl.pallas_call(
        kernel,
        grid=(B, H // hb, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, 1), jnp.float32),
            pltpu.VMEM((hb, block_q, 1), jnp.float32),
            pltpu.VMEM((hb, block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="flash_attention",
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
