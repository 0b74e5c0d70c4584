"""Shared neural-net layers (functional, param-dict convention).

Every module is a triple of functions:
  init_*(key, ...) -> params pytree (nested dicts of arrays)
  *_specs(...)     -> matching pytree of PartitionSpec
  apply-style function taking (params, x, ...)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.registry import get_op


def dense_init(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in or shape[0]
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


# --- norms -------------------------------------------------------------------

def init_norm(d, norm_type="rmsnorm"):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_specs(norm_type="rmsnorm"):
    p = {"scale": P(None)}
    if norm_type == "layernorm":
        p["bias"] = P(None)
    return p


def apply_norm(p, x, eps=1e-5, kernel=None):
    """LayerNorm (bias present) stays inline jnp; RMSNorm routes through the
    kernel registry (``rmsnorm`` op) so the backend follows ``kernel`` —
    the ref oracle is numerically identical to the historical inline code.
    Its ops carry the ``norm`` scope, which the device trace reads."""
    with jax.named_scope("norm"):
        if "bias" in p:
            xf = x.astype(jnp.float32)
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.var(xf, axis=-1, keepdims=True)
            out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] \
                + p["bias"]
            return out.astype(x.dtype)
        op = get_op("rmsnorm", cfg=kernel, eps=eps)
        return op(x.reshape(-1, x.shape[-1]), p["scale"]).reshape(x.shape)


# --- rotary embeddings --------------------------------------------------------

def rope_freqs(head_dim, theta=1e4):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta=1e4):
    """x: (..., L, H, hd); positions: broadcastable to (..., L)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                                # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., L, hd/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length, d):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / d))
    pe = jnp.zeros((length, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# --- dense FFN ---------------------------------------------------------------

def init_ffn(key, d_model, d_ff, glu=True, bias=False, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
         "w_out": dense_init(ks[1], (d_ff, d_model), fan_in=d_ff, dtype=dtype)}
    if glu:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), dtype=dtype)
    if bias:
        p["b_in"] = jnp.zeros((d_ff,), dtype)
        p["b_out"] = jnp.zeros((d_model,), dtype)
    return p


def ffn_specs(mesh, mp_axes, d_ff, glu=True, bias=False):
    from repro.parallel.mesh import axis_size
    ff_ax = tuple(mp_axes) if mp_axes and d_ff % axis_size(mesh, mp_axes) == 0 \
        else None
    p = {"w_in": P(None, ff_ax), "w_out": P(ff_ax, None)}
    if glu:
        p["w_gate"] = P(None, ff_ax)
    if bias:
        p["b_in"] = P(ff_ax)
        p["b_out"] = P(None)
    return p


def apply_ffn(p, x, act="silu"):
    """The dense FFN, under the ``ffn`` scope."""
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[act]
    with jax.named_scope("ffn"):
        h = x @ p["w_in"]
        if "b_in" in p:
            h = h + p["b_in"]
        if "w_gate" in p:
            h = actf(x @ p["w_gate"]) * h
        else:
            h = actf(h)
        out = h @ p["w_out"]
        if "b_out" in p:
            out = out + p["b_out"]
        return out


# --- embeddings ---------------------------------------------------------------

def init_embedding(key, vocab, d_model, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embedding_specs(mesh, mp_axes, vocab):
    from repro.parallel.mesh import axis_size
    v_ax = tuple(mp_axes) if mp_axes and vocab % axis_size(mesh, mp_axes) == 0 \
        else None
    return {"table": P(v_ax, None)}


def embed(p, ids):
    return p["table"][ids]


def unembed(p, x):
    return x @ p["table"].T
