"""Pure-jnp oracles for every Pallas kernel (the ground truth the
interpret-mode kernels are asserted against, shape/dtype-swept)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None,
                        q_offset=None):
    """q,k,v: (B, L, H, hd) (kv already head-repeated). Returns (B, L, H, hd).
    ``q_offset``: the position of q's first row (default 0), for a block
    of query rows against all the keys."""
    B, Lq, H, hd = q.shape
    Lk = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qp = jnp.arange(Lq)[:, None]
    if q_offset is not None:
        qp = qp + q_offset
    kp = jnp.arange(Lk)[None, :]
    ok = jnp.ones((Lq, Lk), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= qp - kp < window
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def expert_ffn_ref(x, w1, w3, w2, *, act="silu"):
    """Grouped expert FFN. x: (E, T, M); w1/w3: (E, M, F); w2: (E, F, M)."""
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    h = jnp.einsum("etm,emf->etf", x, w1)
    if w3 is not None:
        h = actf(h) * jnp.einsum("etm,emf->etf", x, w3)
    else:
        h = actf(h)
    return jnp.einsum("etf,efm->etm", h, w2)


def expert_ffn_ragged_ref(xb, counts, w1, w3, w2, *, act="silu"):
    """Ragged grouped FFN over a (E, G, c, M) pool with per-(expert,
    group) valid-row counts.  Compute runs in f32 (matching the pool
    path's decoded payloads) and rows at index >= counts[e, g] are
    forced to exact zero — the dropless contract: padding rows carry no
    FLOPs semantically and no value numerically.  Output is cast back
    to ``xb.dtype`` (the wire dtype on the fused raw path)."""
    E, G, c, M = xb.shape
    h = expert_ffn_ref(xb.reshape(E, G * c, M).astype(jnp.float32),
                       w1, w3, w2, act=act)
    mask = jnp.arange(c)[None, None, :] < counts[:, :, None]
    h = h.reshape(E, G, c, M) * mask[..., None].astype(h.dtype)
    return h.astype(xb.dtype)


def expert_ffn_grouped_ref(x, flat_idx, weights, w1, w3, w2, *,
                           cap, act="silu", wire="f32"):
    """Single-device fused megakernel oracle: dispatch gather ->
    (wire decode) -> expert FFN -> (wire encode/decode) -> combine
    scatter + weight-dot, one op.  ``wire`` in {"f32", "bf16"} models
    the fused codec as a round-trip at the two pool boundaries,
    matching what dispatch_a2a/combine_a2a do between the unfused ops.

    x: (S, M); flat_idx/weights: (S, k); returns (S, M) in x.dtype."""
    E = w1.shape[0]

    def rt(v):   # fused wire round-trip at a pool boundary
        return v.astype(jnp.bfloat16).astype(v.dtype) if wire == "bf16" \
            else v
    buf = rt(moe_dispatch_ref(x, flat_idx, E * cap))
    h = expert_ffn_ref(buf.reshape(E, cap, -1).astype(jnp.float32),
                       w1, w3, w2, act=act)
    h = rt(h.reshape(E * cap, -1))
    return moe_combine_ref(h, flat_idx, weights).astype(x.dtype)


def moe_dispatch_ref(x, flat_idx, n_slots):
    """Scatter tokens into the flat capacity buffer.

    x: (S, M); flat_idx: (S, k) int32 in [0, n_slots] (n_slots = drop).
    Returns (n_slots, M).
    """
    S, M = x.shape
    k = flat_idx.shape[1]
    buf = jnp.zeros((n_slots + 1, M), x.dtype)
    src = jnp.broadcast_to(x[:, None, :], (S, k, M)).reshape(S * k, M)
    buf = buf.at[flat_idx.reshape(-1)].add(src, mode="drop")
    return buf[:-1]


def moe_combine_ref(buf, flat_idx, weights):
    """Gather expert outputs back to tokens. buf: (n_slots, M);
    flat_idx: (S, k); weights: (S, k). Returns (S, M)."""
    n_slots, M = buf.shape
    idx = jnp.minimum(flat_idx, n_slots - 1)
    vals = buf[idx.reshape(-1)].reshape(*flat_idx.shape, M)
    w = jnp.where(flat_idx < n_slots, weights, 0.0)
    return jnp.einsum("sk,skm->sm", w.astype(buf.dtype), vals)


def rmsnorm_ref(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)
