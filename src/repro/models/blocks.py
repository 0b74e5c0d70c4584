"""Transformer-family blocks, one per layer kind.

Kinds: dense(_full), moe(_full), cross, xdec, hymba, mlstm, slstm, encoder.
Each kind provides init / specs / train-apply / decode-apply with a shared
signature so model.py can stack same-kind runs and lax.scan over them.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.registry import KernelConfig
from repro.core.moe import (MoEConfig, apply_moe, init_moe_params,
                            moe_param_specs)
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnConfig
from repro.models.layers import (apply_ffn, apply_norm, ffn_specs, init_ffn,
                                 init_norm, norm_specs)


def base_kind(kind: str) -> str:
    return kind[:-5] if kind.endswith("_full") else kind


def attn_config(cfg: ModelConfig, kind: str, cross: bool = False) -> AttnConfig:
    full = kind.endswith("_full")
    return AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope and not full and not cross,
        causal=not cross and cfg.arch_type != "encoder",
        window=None if (full or cross) else cfg.attn_window,
        chunk=None if (full or cross) else cfg.attn_chunk,
        qkv_bias=cfg.qkv_bias and not cross,
        qk_norm=cfg.qk_norm and not cross, norm_eps=cfg.norm_eps,
        masked_cache_update=cfg.cache_masked_update,
        context_parallel=cfg.context_parallel_decode)


def _has_ffn(kind: str) -> bool:
    return kind not in ("mlstm",)


def _moe_kind(kind: str) -> bool:
    return kind.startswith("moe")


def _moe_cfg(cfg: ModelConfig, kcfg: KernelConfig) -> MoEConfig:
    """MoE config with the model-level kernel pin inherited: the MoE
    config's own (non-default) kernel wins, otherwise the block-level
    choice — incl. the legacy ``use_pallas`` flag — flows through."""
    if cfg.moe.kernel == KernelConfig() and kcfg != cfg.moe.kernel:
        return replace(cfg.moe, kernel=kcfg)
    return cfg.moe


# --- init / specs -------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    p = {"norm1": init_norm(cfg.d_model, cfg.norm_type)}
    base = base_kind(kind)

    if base in ("dense", "moe", "cross", "xdec", "hymba", "encoder"):
        p["attn"] = attn_mod.init_attn(ks[0], attn_config(cfg, kind), dtype)
    if base == "cross" or base == "xdec":
        p["xattn"] = attn_mod.init_attn(
            ks[1], attn_config(cfg, kind, cross=True), dtype)
        p["norm_x"] = init_norm(cfg.d_model, cfg.norm_type)
        if base == "cross":
            p["gate_attn"] = jnp.zeros((), jnp.float32)
            p["gate_ffn"] = jnp.zeros((), jnp.float32)
    if base == "hymba":
        p["mamba"] = ssm_mod.init_mamba(ks[2], _mamba_cfg(cfg), dtype)
        p["norm_a"] = init_norm(cfg.d_model, cfg.norm_type)
        p["norm_s"] = init_norm(cfg.d_model, cfg.norm_type)
    if base == "mlstm":
        p["mlstm"] = ssm_mod.init_mlstm(ks[3], _mlstm_cfg(cfg), dtype)
    if base == "slstm":
        p["slstm"] = ssm_mod.init_slstm(ks[4], _slstm_cfg(cfg), dtype)

    if _moe_kind(kind):
        p["moe"] = init_moe_params(ks[5], cfg.moe, dtype)
        p["norm2"] = init_norm(cfg.d_model, cfg.norm_type)
    elif _has_ffn(base) and cfg.d_ff:
        p["ffn"] = init_ffn(ks[6], cfg.d_model,
                            _ffn_width(cfg, base), glu=cfg.glu,
                            bias=cfg.ffn_bias, dtype=dtype)
        if not cfg.parallel_block:
            p["norm2"] = init_norm(cfg.d_model, cfg.norm_type)
    return p


def _ffn_width(cfg: ModelConfig, base: str) -> int:
    if base == "slstm" and not cfg.d_ff:
        return int(cfg.d_model * 4 / 3)
    return cfg.d_ff


def _mamba_cfg(cfg: ModelConfig) -> ssm_mod.MambaConfig:
    return ssm_mod.MambaConfig(
        d_model=cfg.d_model, d_inner=int(cfg.d_model * cfg.ssm_expand),
        d_state=cfg.ssm_state, d_conv=cfg.ssm_conv)


def _mlstm_cfg(cfg: ModelConfig) -> ssm_mod.MLSTMConfig:
    return ssm_mod.MLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_kv_heads)


def _slstm_cfg(cfg: ModelConfig) -> ssm_mod.SLSTMConfig:
    return ssm_mod.SLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_kv_heads)


def block_specs(cfg: ModelConfig, kind: str, mesh, dims) -> dict:
    mp = dims.mp
    s = {"norm1": norm_specs(cfg.norm_type)}
    base = base_kind(kind)
    if base in ("dense", "moe", "cross", "xdec", "hymba", "encoder"):
        s["attn"] = attn_mod.attn_specs(mesh, mp, attn_config(cfg, kind))
    if base in ("cross", "xdec"):
        s["xattn"] = attn_mod.attn_specs(mesh, mp,
                                         attn_config(cfg, kind, cross=True))
        s["norm_x"] = norm_specs(cfg.norm_type)
        if base == "cross":
            s["gate_attn"] = P()
            s["gate_ffn"] = P()
    if base == "hymba":
        s["mamba"] = ssm_mod.mamba_specs(mesh, mp, _mamba_cfg(cfg))
        s["norm_a"] = norm_specs(cfg.norm_type)
        s["norm_s"] = norm_specs(cfg.norm_type)
    if base == "mlstm":
        s["mlstm"] = ssm_mod.mlstm_specs(mesh, mp, _mlstm_cfg(cfg))
    if base == "slstm":
        s["slstm"] = ssm_mod.slstm_specs(mesh, mp, _slstm_cfg(cfg))
    if _moe_kind(kind):
        s["moe"] = moe_param_specs(cfg.moe, mesh, dims)
        s["norm2"] = norm_specs(cfg.norm_type)
    elif _has_ffn(base) and cfg.d_ff:
        s["ffn"] = ffn_specs(mesh, mp, _ffn_width(cfg, base), glu=cfg.glu,
                             bias=cfg.ffn_bias)
        if not cfg.parallel_block:
            s["norm2"] = norm_specs(cfg.norm_type)
    return s


# --- train/prefill apply --------------------------------------------------------

def apply_block(p, cfg: ModelConfig, kind: str, x, *, mesh, dims,
                ctx=None, positions=None, schedule=None):
    """Full-sequence forward. Returns ``(x, aux)`` where ``aux`` is a dict:
    ``loss`` the scalar router-loss contribution and ``expert_load`` the
    per-expert routed-row counts — (E,) for MoE kinds, (0,) otherwise so
    every kind scans with the same pytree structure."""
    base = base_kind(kind)
    acfg = attn_config(cfg, kind)
    aux = {"loss": jnp.float32(0.0),
           "expert_load": jnp.zeros((0,), jnp.float32)}
    eps = cfg.norm_eps
    kcfg = cfg.kernel_cfg

    def norm(pn, h):
        return apply_norm(pn, h, eps, kcfg)

    if base in ("dense", "moe", "encoder"):
        h = norm(p["norm1"], x)
        a = attn_mod.apply_attn(p["attn"], acfg, h, positions=positions,
                                kernel=kcfg, mesh=mesh, dims=dims)
        if cfg.parallel_block:
            f = apply_ffn(p["ffn"], h, cfg.ffn_act)
            # sum the two partial (row-parallel) outputs BEFORE they meet
            # the replicated residual: one AllReduce instead of two (§Perf B1)
            return x + (a + f), aux
        x = x + a
        h2 = norm(p["norm2"], x)
        if _moe_kind(kind):
            y, moe_aux = apply_moe(h2, p["moe"], mesh=mesh, dims=dims,
                                   cfg=_moe_cfg(cfg, kcfg), schedule=schedule)
            aux = {"loss": aux["loss"] + moe_aux["aux_loss"]
                   + moe_aux["z_loss"],
                   "expert_load": moe_aux["expert_load"]}
        else:
            y = apply_ffn(p["ffn"], h2, cfg.ffn_act)
        return x + y, aux

    if base == "cross":
        # llama3.2-vision style gated cross-attention layer
        h = norm(p["norm1"], x)
        a = attn_mod.apply_attn(p["xattn"], attn_config(cfg, kind, True),
                                h, kv_x=ctx)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * a
        h2 = norm(p["norm_x"], x)
        f = apply_ffn(p["ffn"], h2, cfg.ffn_act)
        return x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * f, aux

    if base == "xdec":
        # whisper decoder: self-attn + cross-attn + FFN
        h = norm(p["norm1"], x)
        x = x + attn_mod.apply_attn(p["attn"], acfg, h, positions=positions,
                                    kernel=kcfg, mesh=mesh, dims=dims)
        h = norm(p["norm_x"], x)
        x = x + attn_mod.apply_attn(p["xattn"],
                                    attn_config(cfg, kind, True), h, kv_x=ctx)
        h = norm(p["norm2"], x)
        return x + apply_ffn(p["ffn"], h, cfg.ffn_act), aux

    if base == "hymba":
        h = norm(p["norm1"], x)
        a = attn_mod.apply_attn(p["attn"], acfg, h, positions=positions,
                                kernel=kcfg, mesh=mesh, dims=dims)
        s = ssm_mod.apply_mamba(p["mamba"], _mamba_cfg(cfg), h)
        x = x + 0.5 * (norm(p["norm_a"], a)
                       + norm(p["norm_s"], s))
        h2 = norm(p["norm2"], x)
        return x + apply_ffn(p["ffn"], h2, cfg.ffn_act), aux

    if base == "mlstm":
        h = norm(p["norm1"], x)
        return x + ssm_mod.apply_mlstm(p["mlstm"], _mlstm_cfg(cfg), h), aux

    if base == "slstm":
        h = norm(p["norm1"], x)
        x = x + ssm_mod.apply_slstm(p["slstm"], _slstm_cfg(cfg), h)
        if "ffn" in p:
            h2 = norm(p["norm2"], x)
            x = x + apply_ffn(p["ffn"], h2, cfg.ffn_act)
        return x, aux

    raise ValueError(f"unknown block kind {kind}")


# --- decode apply ---------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=jnp.float32) -> dict:
    base = base_kind(kind)
    c = {}
    acfg = attn_config(cfg, kind)
    if base in ("dense", "moe", "xdec", "hymba", "encoder"):
        c["attn"] = attn_mod.init_cache(acfg, batch, max_len, dtype)
    if base == "hymba":
        c["mamba"] = ssm_mod.init_mamba_state(_mamba_cfg(cfg), batch, dtype)
    if base == "mlstm":
        c["mlstm"] = ssm_mod.init_mlstm_state(_mlstm_cfg(cfg), batch)
    if base == "slstm":
        c["slstm"] = ssm_mod.init_slstm_state(_slstm_cfg(cfg), batch)
    if base == "cross":
        c["dummy"] = jnp.zeros((), dtype)  # static ctx K/V built per request
    return c


def prefill_block(p, cfg: ModelConfig, kind: str, x, cache, lengths, *,
                  mesh, dims, schedule=None):
    """Whole-prompt block forward that also fills the decode cache.

    The serving engine's batched one-shot prefill: identical math to
    ``apply_block`` plus the KV-cache write of ``prefill_attn``.  Only
    attention-backed kinds participate (SSM/cross archs would need
    recurrent-state extraction; the engine rejects them up front).
    Returns (x, new_cache).
    """
    base = base_kind(kind)
    if base not in ("dense", "moe"):
        raise NotImplementedError(
            f"prefill_block: kind {kind!r} has no cache-filling prefill "
            "(serving engine supports dense/moe decoder stacks)")
    acfg = attn_config(cfg, kind)
    eps = cfg.norm_eps
    kcfg = cfg.kernel_cfg

    def norm(pn, h):
        return apply_norm(pn, h, eps, kcfg)

    h = norm(p["norm1"], x)
    a, c2 = attn_mod.prefill_attn(p["attn"], acfg, h, cache["attn"],
                                  lengths, kernel=kcfg, mesh=mesh,
                                  dims=dims)
    new_cache = dict(cache)
    new_cache["attn"] = c2
    if cfg.parallel_block:
        f = apply_ffn(p["ffn"], h, cfg.ffn_act)
        return x + (a + f), new_cache
    x = x + a
    h2 = norm(p["norm2"], x)
    if _moe_kind(kind):
        # prefill pools are training-shaped: the MoE layer takes the
        # *prefill* autosched decision (infer=False), distinct from the
        # decode decision the same layer makes under decode_block
        y, _ = apply_moe(h2, p["moe"], mesh=mesh, dims=dims,
                         cfg=_moe_cfg(cfg, kcfg), schedule=schedule)
    else:
        y = apply_ffn(p["ffn"], h2, cfg.ffn_act)
    return x + y, new_cache


def paged_block(p, cfg: ModelConfig, kind: str, x, cache, table, starts,
                lens, *, mesh, dims, schedule=None, infer=False,
                with_aux=False):
    """Block forward over a paged KV arena: the ONE code path behind the
    serving engine's decode (C=1, ``infer=True``), one-shot prefill and
    chunked prefill (``infer=False`` — prefill pools take the training-
    shaped autosched decision, like ``prefill_block``).  Routing every
    phase through the same primitive is what makes chunked-vs-one-shot
    and prefix-hit-vs-cold runs bitwise comparable.  Returns
    ``(x, new_cache)``, or ``(x, new_cache, expert_load)`` with
    ``with_aux=True`` — the (E,) per-expert routed-row counts ((0,) for
    dense blocks) feeding the serving engine's load EMA; the default
    keeps every existing caller's arity.
    """
    base = base_kind(kind)
    if base not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged_block: kind {kind!r} has no paged-cache path "
            "(serving engine supports dense/moe decoder stacks)")
    acfg = attn_config(cfg, kind)
    eps = cfg.norm_eps
    kcfg = cfg.kernel_cfg

    def norm(pn, h):
        return apply_norm(pn, h, eps, kcfg)

    h = norm(p["norm1"], x)
    a, c2 = attn_mod.paged_chunk_attn(p["attn"], acfg, h, cache["attn"],
                                      table, starts, lens)
    new_cache = dict(cache)
    new_cache["attn"] = c2
    no_load = jnp.zeros((0,), jnp.float32)
    if cfg.parallel_block:
        f = apply_ffn(p["ffn"], h, cfg.ffn_act)
        out = x + (a + f)
        return (out, new_cache, no_load) if with_aux else (out, new_cache)
    x = x + a
    h2 = norm(p["norm2"], x)
    load = no_load
    if _moe_kind(kind):
        y, maux = apply_moe(h2, p["moe"], mesh=mesh, dims=dims,
                            cfg=_moe_cfg(cfg, kcfg), schedule=schedule,
                            infer=infer)
        load = maux["expert_load"]
    else:
        y = apply_ffn(p["ffn"], h2, cfg.ffn_act)
    out = x + y
    return (out, new_cache, load) if with_aux else (out, new_cache)


def decode_block(p, cfg: ModelConfig, kind: str, x, cache, step, *,
                 mesh, dims, ctx_kv=None, schedule=None):
    """One-token decode. Returns (x, new_cache)."""
    base = base_kind(kind)
    acfg = attn_config(cfg, kind)
    eps = cfg.norm_eps
    kcfg = cfg.kernel_cfg
    new_cache = dict(cache)

    def norm(pn, h):
        return apply_norm(pn, h, eps, kcfg)

    def self_attn(h):
        # context-parallel decode: with an idle batch dim (B=1) the cache
        # length is sharded over the batch axes too (§Perf C6).
        ctx_axes = tuple(dims.mp) if x.shape[0] > 1 \
            else tuple(dims.batch_axes) + tuple(dims.mp)
        a, c2 = attn_mod.decode_attn(p["attn"], acfg, h, cache["attn"], step,
                                     mesh=mesh, mp_axes=ctx_axes)
        new_cache["attn"] = c2
        return a

    if base in ("dense", "moe", "encoder"):
        h = norm(p["norm1"], x)
        a = self_attn(h)
        if cfg.parallel_block:
            f = apply_ffn(p["ffn"], h, cfg.ffn_act)
            return x + (a + f), new_cache
        x = x + a
        h2 = norm(p["norm2"], x)
        if _moe_kind(kind):
            # infer=True: decode shape class — own autosched cache line,
            # decode-widened grid (s1d), drop-free capacity
            y, _ = apply_moe(h2, p["moe"], mesh=mesh, dims=dims,
                             cfg=_moe_cfg(cfg, kcfg), schedule=schedule,
                             infer=True)
        else:
            y = apply_ffn(p["ffn"], h2, cfg.ffn_act)
        return x + y, new_cache

    if base == "cross":
        h = norm(p["norm1"], x)
        a, _ = attn_mod.decode_attn(p["xattn"], attn_config(cfg, kind, True),
                                    h, None, step, kv_cache_static=ctx_kv)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * a
        h2 = norm(p["norm_x"], x)
        f = apply_ffn(p["ffn"], h2, cfg.ffn_act)
        return x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * f, new_cache

    if base == "xdec":
        h = norm(p["norm1"], x)
        x = x + self_attn(h)
        h = norm(p["norm_x"], x)
        a, _ = attn_mod.decode_attn(p["xattn"], attn_config(cfg, kind, True),
                                    h, None, step, kv_cache_static=ctx_kv)
        x = x + a
        h = norm(p["norm2"], x)
        return x + apply_ffn(p["ffn"], h, cfg.ffn_act), new_cache

    if base == "hymba":
        h = norm(p["norm1"], x)
        a = self_attn(h)
        s, st = ssm_mod.apply_mamba(p["mamba"], _mamba_cfg(cfg), h,
                                    state=cache["mamba"])
        new_cache["mamba"] = st
        x = x + 0.5 * (norm(p["norm_a"], a)
                       + norm(p["norm_s"], s))
        h2 = norm(p["norm2"], x)
        return x + apply_ffn(p["ffn"], h2, cfg.ffn_act), new_cache

    if base == "mlstm":
        h = norm(p["norm1"], x)
        y, st = ssm_mod.apply_mlstm(p["mlstm"], _mlstm_cfg(cfg), h,
                                    state=cache["mlstm"])
        new_cache["mlstm"] = st
        return x + y, new_cache

    if base == "slstm":
        h = norm(p["norm1"], x)
        y, st = ssm_mod.apply_slstm(p["slstm"], _slstm_cfg(cfg), h,
                                    state=cache["slstm"])
        new_cache["slstm"] = st
        x = x + y
        if "ffn" in p:
            h2 = norm(p["norm2"], x)
            x = x + apply_ffn(p["ffn"], h2, cfg.ffn_act)
        return x, new_cache

    raise ValueError(f"unknown block kind {kind}")
