"""Attention: GQA / MHA, causal, sliding-window, chunked-local and cross.

Two execution paths share one parameter layout:
  * full einsum attention for short sequences (and as the oracle),
  * a flash-style KV-block scan (online softmax, pure jnp + lax.scan) for
    long sequences — memory O(L * block) instead of O(L^2), lowerable on
    any backend; the Pallas TPU kernel (repro.kernels.flash_attention)
    implements the same contract with explicit VMEM tiling.

Decode: one query token against a KV cache; sliding-window caches are
ring buffers of size ``window``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_rope, dense_init


@dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 1e4
    use_rope: bool = True
    causal: bool = True
    window: int | None = None     # sliding window (tokens), None = full
    chunk: int | None = None      # llama4-style chunked local attention
    qkv_bias: bool = False
    softmax_scale: float | None = None
    flash_block: int = 512        # KV block for the scan path
    flash_threshold: int = 2048   # use scan path above this seq length
    masked_cache_update: bool = False  # elementwise cache write (§Perf C2)
    context_parallel: bool = False     # shard scores over cache length (§Perf C3)

    @property
    def scale(self):
        return self.softmax_scale or 1.0 / math.sqrt(self.head_dim)


def init_attn(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], (D, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (D, K * hd), dtype=dtype),
        "wv": dense_init(ks[2], (D, K * hd), dtype=dtype),
        "wo": dense_init(ks[3], (H * hd, D), fan_in=H * hd, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def attn_specs(mesh, mp_axes, cfg: AttnConfig):
    from repro.parallel.mesh import axis_size
    n_mp = axis_size(mesh, mp_axes) if mp_axes else 1
    q_ax = tuple(mp_axes) if mp_axes and (cfg.n_heads * cfg.head_dim) % n_mp == 0 \
        else None
    kv_ax = tuple(mp_axes) if mp_axes and cfg.n_kv_heads % n_mp == 0 else None
    kv_sp = tuple(mp_axes) if kv_ax else None
    p = {"wq": P(None, q_ax), "wk": P(None, kv_sp), "wv": P(None, kv_sp),
         "wo": P(q_ax, None)}
    if cfg.qkv_bias:
        p["bq"] = P(q_ax)
        p["bk"] = P(kv_sp)
        p["bv"] = P(kv_sp)
    return p


def _mask_bias(cfg: AttnConfig, q_pos, k_pos):
    """Additive mask from query/key absolute positions."""
    d = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones_like(d, dtype=bool)
    if cfg.causal:
        ok &= d >= 0
    if cfg.window is not None:
        ok &= d < cfg.window
    if cfg.chunk is not None:
        ok &= (q_pos[:, None] // cfg.chunk) == (k_pos[None, :] // cfg.chunk)
    # finite mask constant: fully-masked KV blocks stay NaN-free in the
    # online softmax (exp(-inf - -inf) is NaN; -1e30 self-corrects via the
    # running-max rescale) and give exactly-zero probabilities in the
    # recompute backward.
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def sdpa_full(q, k, v, bias, scale):
    """q: (B,Lq,H,hd)  k,v: (B,Lk,H,hd)  bias: (Lq,Lk) or (B,1,Lq,Lk)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = s + (bias if bias.ndim == 4 else bias[None, None])
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def sdpa_flash_scan(q, k, v, cfg: AttnConfig, q_pos, k_pos):
    """Online-softmax attention scanning KV blocks; O(L*block) memory in
    BOTH directions: a recompute-based custom_vjp stores only (out, lse)
    and rebuilds each block's probabilities in the backward pass — the
    flash-attention backward.  (Scan's default AD saved every block's
    probability tile + f32 accumulator carry: ~130 GB/chip for command-r
    train_4k — EXPERIMENTS.md §Perf D3/D4.)"""
    blk = min(cfg.flash_block, k.shape[1])
    while k.shape[1] % blk:
        blk //= 2

    @jax.custom_vjp
    def attn(q, k, v, q_pos, k_pos):
        out, lse = _flash_fwd_scan(q, k, v, cfg, q_pos, k_pos, blk)
        return out

    def fwd(q, k, v, q_pos, k_pos):
        out, lse = _flash_fwd_scan(q, k, v, cfg, q_pos, k_pos, blk)
        return out, (q, k, v, out, lse, q_pos, k_pos)

    def bwd(res, dout):
        *res5, q_pos, k_pos = res
        dq, dk, dv = _flash_bwd_scan(tuple(res5), dout, cfg, q_pos,
                                     k_pos, blk)
        return dq, dk, dv, None, None

    attn.defvjp(fwd, bwd)
    return attn(q, k, v, q_pos, k_pos)


def _flash_fwd_scan(q, k, v, cfg: AttnConfig, q_pos, k_pos, blk):
    B, Lq, H, hd = q.shape
    n_blocks = k.shape[1] // blk
    qf = q.astype(jnp.float32) * cfg.scale

    def step(carry, blk_idx):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, blk_idx * blk, blk, axis=1)
        vs = lax.dynamic_slice_in_dim(v, blk_idx * blk, blk, axis=1)
        kp = lax.dynamic_slice_in_dim(k_pos, blk_idx * blk, blk, axis=0)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ks.astype(jnp.float32))
        s = s + _mask_bias(cfg, q_pos, kp)[None, None]
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vs.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Lq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    a0 = jnp.zeros((B, H, Lq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), jnp.arange(n_blocks))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    lse = m + jnp.log(l)                                  # (B, H, Lq)
    return out, lse


def _flash_bwd_scan(res, dout, cfg: AttnConfig, q_pos, k_pos, blk):
    q, k, v, out, lse = res
    B, Lq, H, hd = q.shape
    n_blocks = k.shape[1] // blk
    qf = q.astype(jnp.float32) * cfg.scale
    do = dout.astype(jnp.float32).transpose(0, 2, 1, 3)   # (B, H, Lq, hd)
    of = out.astype(jnp.float32).transpose(0, 2, 1, 3)
    D = jnp.sum(do * of, axis=-1)                         # (B, H, Lq)

    def step(dq, blk_idx):
        ks = lax.dynamic_slice_in_dim(k, blk_idx * blk, blk, axis=1)
        vs = lax.dynamic_slice_in_dim(v, blk_idx * blk, blk, axis=1)
        kp = lax.dynamic_slice_in_dim(k_pos, blk_idx * blk, blk, axis=0)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ks.astype(jnp.float32))
        s = s + _mask_bias(cfg, q_pos, kp)[None, None]
        p = jnp.exp(s - lse[..., None])                   # (B, H, Lq, blk)
        dv_b = jnp.einsum("bhqk,bhqd->bkhd", p, do)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, vs.astype(jnp.float32))
        ds = p * (dp - D[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds,
                             ks.astype(jnp.float32)) * cfg.scale
        dk_b = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        return dq, (dk_b, dv_b)

    dq0 = jnp.zeros((B, Lq, H, hd), jnp.float32)
    dq, (dks, dvs) = lax.scan(step, dq0, jnp.arange(n_blocks))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(k.shape)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(v.shape)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def _flash_op(cfg: AttnConfig, kernel, q, k, mesh, dims):
    """The registry's ``flash_attention`` op for (B, L, H, hd) q and
    (B, L, K, hd) k/v.  GSPMD cannot partition a Mosaic kernel, so on a
    multi-device mesh the op runs under ``shard_map``: batch over the
    batch axes and heads over MP, each only where it divides evenly."""
    from repro.kernels.registry import get_op
    from repro.parallel.mesh import axis_size
    op = get_op("flash_attention", cfg=kernel, causal=cfg.causal,
                window=cfg.window, scale=cfg.scale)
    if mesh is None or mesh.devices.size == 1:
        return op
    bx, hx = tuple(dims.batch_axes), tuple(dims.mp)
    b_ax = bx if bx and q.shape[0] % axis_size(mesh, bx) == 0 else None
    h_ax = hx if hx and k.shape[2] % axis_size(mesh, hx) == 0 else None
    spec = P(b_ax, None, h_ax, None)
    return jax.shard_map(op, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def apply_attn(p, cfg: AttnConfig, x, *, positions=None, kv_x=None,
               kv_positions=None, use_pallas=False, kernel=None,
               mesh=None, dims=None):
    """Training/prefill forward. kv_x != None = cross attention.

    Kernel-backend selection: ``use_pallas=True`` (legacy flag) or a
    ``kernel`` config resolving to ``"pallas"`` routes self-attention
    through the registry's ``flash_attention`` op; otherwise the jnp paths
    below (full sdpa / online-softmax scan) run — they ARE the reference
    implementation, with masking modes the kernel doesn't cover (chunked
    local attention, arbitrary position vectors).  ``mesh``/``dims``
    place the kernel per shard on a multi-device mesh.  Its ops, the
    projections and the core, carry the ``attn`` scope, which the device
    trace reads.
    """
    with jax.named_scope("attn"):
        B, L, D = x.shape
        H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        src = kv_x if kv_x is not None else x
        Lk = src.shape[1]
        q = (x @ p["wq"]).reshape(B, L, H, hd)
        k = (src @ p["wk"]).reshape(B, Lk, K, hd)
        v = (src @ p["wv"]).reshape(B, Lk, K, hd)
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(H, hd)
            k = k + p["bk"].reshape(K, hd)
            v = v + p["bv"].reshape(K, hd)
        # the Pallas kernel derives positions from block indices, so it is
        # only valid for the default contiguous-from-zero layout (record
        # before the arange defaults are filled in)
        contiguous_pos = positions is None and kv_positions is None
        if positions is None:
            positions = jnp.arange(L)
        if kv_positions is None:
            kv_positions = jnp.arange(Lk)
        if cfg.use_rope and kv_x is None:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, kv_positions, cfg.rope_theta)
        from repro.kernels.registry import resolve_backend
        want_pallas = use_pallas or (
            kernel is not None and resolve_backend(cfg=kernel) == "pallas")
        # the kernel handles causal/window masks over contiguous
        # positions only
        kernel_ok = cfg.chunk is None and kv_x is None and contiguous_pos
        if want_pallas and kernel_ok:
            # KV stays in its native GQA layout — the kernel's index
            # map folds the query-head -> kv-head mapping, no repeat
            # ever hits HBM
            out = _flash_op(cfg, kernel, q, k, mesh, dims)(q, k, v)
        else:
            k = _repeat_kv(k, H // K)
            v = _repeat_kv(v, H // K)
            if max(L, Lk) > cfg.flash_threshold:
                out = sdpa_flash_scan(q, k, v, cfg, positions, kv_positions)
            else:
                bias = _mask_bias(cfg, positions, kv_positions) if (
                    cfg.causal or cfg.window or cfg.chunk) else jnp.zeros(
                        (L, Lk), jnp.float32)
                out = sdpa_full(q, k, v, bias, cfg.scale)
        return out.reshape(B, L, H * hd) @ p["wo"]


# --- decode with KV cache -----------------------------------------------------

def init_cache(cfg: AttnConfig, batch, max_len, dtype=jnp.float32):
    W = cfg.window if cfg.window is not None else max_len
    W = min(W, max_len)
    return {
        "k": jnp.zeros((batch, W, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, W, cfg.n_kv_heads, cfg.head_dim), dtype),
        # absolute position per slot, tracked per row: the serving
        # engine's continuous batching puts every request at its own
        # position, so slot validity is per (row, slot), not per slot
        "pos": jnp.zeros((batch, W), jnp.int32) - 1,
    }


def prefill_attn(p, cfg: AttnConfig, x, cache, lengths, *, kernel=None,
                 mesh=None, dims=None):
    """Batched one-shot prefill: whole-prompt self-attention + KV fill.

    ``x`` is the (B, L, D) right-padded prompt batch, ``lengths`` the
    (B,) valid token counts.  One call computes the causal attention
    over every prompt position AND writes the (rope-rotated) K/V into
    the decode cache at positions ``0..L-1``; the per-row ``pos`` map
    marks only slots ``< lengths[b]`` valid, so padding (and any stale
    K/V from a previous occupant of the cache row) is invisible to later
    decode steps.  Causality keeps padded positions from influencing
    valid ones, so each row's result is independent of how much padding
    its prefill bucket carries.

    Requires a full-length cache (``W >= L``): the engine rejects
    sliding-window archs rather than re-deriving ring-buffer fills.
    """
    B, L, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    W = cache["k"].shape[1]
    if W < L:
        raise ValueError(f"prefill_attn needs cache W={W} >= prompt L={L}")
    q = (x @ p["wq"]).reshape(B, L, H, hd)
    k = (x @ p["wk"]).reshape(B, L, K, hd)
    v = (x @ p["wv"]).reshape(B, L, K, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(H, hd)
        k = k + p["bk"].reshape(K, hd)
        v = v + p["bv"].reshape(K, hd)
    positions = jnp.arange(L)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    # cache fill: K/V land at their absolute positions (post-rope, the
    # same values decode_attn would have written one token at a time)
    widx = jnp.arange(W)
    valid = (widx[None, :] < lengths[:, None]) & (widx < L)[None]
    new_cache = {
        "k": lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
        "v": lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, axis=1),
        "pos": jnp.where(valid, widx[None, :], -1).astype(jnp.int32),
    }

    from repro.kernels.registry import resolve_backend
    want_pallas = kernel is not None and \
        resolve_backend(cfg=kernel) == "pallas"
    if want_pallas and cfg.chunk is None:
        out = _flash_op(cfg, kernel, q, k, mesh, dims)(q, k, v)
    else:
        kk = _repeat_kv(k, H // K)
        vv = _repeat_kv(v, H // K)
        if L > cfg.flash_threshold:
            out = sdpa_flash_scan(q, kk, vv, cfg, positions, positions)
        else:
            out = sdpa_full(q, kk, vv,
                            _mask_bias(cfg, positions, positions),
                            cfg.scale)
    return out.reshape(B, L, H * hd) @ p["wo"], new_cache


def paged_chunk_attn(p, cfg: AttnConfig, x, arena, table, starts, lens):
    """Unified paged attention: ONE primitive for decode, one-shot
    prefill and chunked prefill, reading/writing the block arena through
    per-row page tables.

    ``x`` is a (B, C, D) chunk of per-row token spans: row b holds
    ``lens[b]`` valid tokens at absolute positions ``starts[b] ..
    starts[b] + lens[b] - 1``.  ``C = 1`` with ``lens = 1`` is a decode
    step; ``starts = 0`` with the whole prompt is one-shot prefill;
    anything between is a prefill chunk.  ``arena`` is this layer's
    paged cache ``{"k","v": (N, bs, Kh, hd), "pos": (N, bs)}`` (physical
    page 0 = the null page), ``table`` the (B, nb) int32 page table.

    The chunk's rope-rotated K/V are scattered into the arena at flat
    page slots ``table[b, p // bs] * bs + p % bs`` (invalid rows target
    the null page and write ``pos = -1``), then every query attends the
    full gathered ``(B, nb * bs)`` context.  Because the gather lays
    position p at index p — exactly the slab cache's layout — and the
    score/softmax op order below matches ``sdpa_full``/``decode_attn``,
    outputs are bit-identical to the slab paths (masked columns are
    exact zeros after softmax; see the paged-vs-slab oracle in
    tests/helpers/run_paged_parity.py).

    Returns ``(out (B, C, D), new_arena)``.
    """
    B, C, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    N, bs = arena["pos"].shape
    nb = table.shape[1]
    q = (x @ p["wq"]).reshape(B, C, H, hd)
    k = (x @ p["wk"]).reshape(B, C, K, hd)
    v = (x @ p["wv"]).reshape(B, C, K, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(H, hd)
        k = k + p["bk"].reshape(K, hd)
        v = v + p["bv"].reshape(K, hd)
    offs = jnp.arange(C)
    qpos = starts[:, None] + offs[None, :]                # (B, C) absolute
    valid_q = offs[None, :] < lens[:, None]               # (B, C)
    if cfg.use_rope:
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, qpos, cfg.rope_theta)

    # scatter the chunk into the arena (flat (N*bs, ...) view): invalid
    # rows/pages land in the null page with pos -1, so they stay masked
    blk_idx = jnp.clip(qpos // bs, 0, nb - 1)
    phys = jnp.take_along_axis(table, blk_idx, axis=1)    # (B, C)
    ok = valid_q & (phys > 0) & (qpos < nb * bs)
    flat = jnp.where(ok, phys * bs + qpos % bs, 0).reshape(-1)
    pos_w = jnp.where(ok, qpos, -1).astype(jnp.int32).reshape(-1)
    # invalid writes are VALUE-zeroed, not just masked: an idle row's
    # hidden state is NaN (its whole context is masked), and a NaN in
    # the null page would leak into live rows through the value einsum
    # (softmax weight 0 * NaN = NaN).  Zeros keep the null page inert
    # AND make the duplicate-index scatter at flat slot 0 deterministic.
    okk = ok.reshape(-1)[:, None, None]
    k_w = jnp.where(okk, k.reshape(-1, K, hd), 0).astype(arena["k"].dtype)
    v_w = jnp.where(okk, v.reshape(-1, K, hd), 0).astype(arena["v"].dtype)
    new_arena = {
        "k": arena["k"].reshape(N * bs, K, hd)
        .at[flat].set(k_w).reshape(N, bs, K, hd),
        "v": arena["v"].reshape(N * bs, K, hd)
        .at[flat].set(v_w).reshape(N, bs, K, hd),
        "pos": arena["pos"].reshape(N * bs)
        .at[flat].set(pos_w).reshape(N, bs),
    }

    # gather each row's full context: page p // bs, offset p % bs —
    # gathered index IS the absolute position (the slab layout)
    gk = jnp.take(new_arena["k"], table, axis=0).reshape(B, nb * bs, K, hd)
    gv = jnp.take(new_arena["v"], table, axis=0).reshape(B, nb * bs, K, hd)
    gpos = jnp.take(new_arena["pos"], table, axis=0).reshape(B, nb * bs)
    kk = _repeat_kv(gk, H // K)
    vv = _repeat_kv(gv, H // K)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * cfg.scale
    gp = gpos[:, None, :]                                 # (B, 1, W)
    qp = qpos[:, :, None]                                 # (B, C, 1)
    valid = (gp >= 0) & (gp <= qp)                        # (B, C, W)
    if cfg.window is not None:
        valid &= gp > qp - cfg.window
    if cfg.chunk is not None:
        valid &= (gp // cfg.chunk) == (qp // cfg.chunk)
    s = jnp.where(valid[:, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, -1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", pr, vv)
    return out.reshape(B, C, H * hd) @ p["wo"], new_arena


def decode_attn(p, cfg: AttnConfig, x, cache, step, *, kv_cache_static=None,
                mesh=None, mp_axes=None):
    """One-token decode. x: (B, 1, D); ``step`` is the absolute position —
    a scalar (classic lockstep serving: every row at the same position)
    or a ``(B,)`` vector (continuous batching: each row at its own).

    Full-attention caches are length max_len; sliding-window caches are
    ring buffers of size ``window`` (slot = pos % window).
    """
    B, _, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    if kv_cache_static is not None:
        # cross-attention: static precomputed K/V (e.g. image/audio context)
        k, v = kv_cache_static["k"], kv_cache_static["v"]
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(H, hd)
        k = _repeat_kv(k, H // K)
        v = _repeat_kv(v, H // K)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * cfg.scale
        pr = jax.nn.softmax(s, -1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
        return out.reshape(B, 1, H * hd) @ p["wo"], cache

    k = (x @ p["wk"]).reshape(B, 1, K, hd)
    v = (x @ p["wv"]).reshape(B, 1, K, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(H, hd)
        k = k + p["bk"].reshape(K, hd)
        v = v + p["bv"].reshape(K, hd)
    vec = jnp.ndim(step) > 0                 # per-row positions (engine)
    if cfg.use_rope:
        pos = step[:, None] if vec else jnp.full((1,), step)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    W = cache["k"].shape[1]
    slot = step % W
    if vec:
        # per-row slot write: each request appends at its own position
        onehot = jnp.arange(W)[None, :] == slot[:, None]      # (B, W)
        ck = jnp.where(onehot[..., None, None],
                       k.astype(cache["k"].dtype), cache["k"])
        cv = jnp.where(onehot[..., None, None],
                       v.astype(cache["v"].dtype), cache["v"])
        cpos = jnp.where(onehot, step[:, None], cache["pos"])
    elif cfg.masked_cache_update:
        # elementwise masked write: partitions cleanly when the cache
        # length dim is sharded (context-parallel decode), unlike a
        # dynamic-update-slice at a data-dependent offset which makes
        # GSPMD all-gather the cache (§Perf C2).
        onehot = (jnp.arange(W) == slot)
        ck = jnp.where(onehot[None, :, None, None],
                       k.astype(cache["k"].dtype), cache["k"])
        cv = jnp.where(onehot[None, :, None, None],
                       v.astype(cache["v"].dtype), cache["v"])
        cpos = jnp.where(onehot[None, :], step, cache["pos"])
    else:
        ck = lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        cpos = cache["pos"].at[:, slot].set(step)
    new_cache = {"k": ck, "v": cv, "pos": cpos}

    kk = _repeat_kv(ck, H // K)
    vv = _repeat_kv(cv, H // K)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * cfg.scale
    if mesh is not None and mp_axes and cfg.context_parallel \
            and W % math.prod(mesh.shape[a] for a in mp_axes) == 0:
        # context-parallel decode (§Perf C3): keep scores sharded along the
        # cache-length dim so GSPMD reshards the tiny query instead of
        # all-gathering the multi-GB K/V cache.
        from jax.sharding import NamedSharding
        s = lax.with_sharding_constraint(
            s, NamedSharding(mesh, P(None, None, None, tuple(mp_axes))))
    step_b = step[:, None] if vec else step
    valid = (cpos >= 0) & (cpos <= step_b)                    # (B, W)
    if cfg.window is not None:
        valid &= cpos > step_b - cfg.window
    if cfg.chunk is not None:
        valid &= (cpos // cfg.chunk) == (step_b // cfg.chunk)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, -1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", pr, vv)
    return out.reshape(B, 1, H * hd) @ p["wo"], new_cache
